"""The benchmark's harness on the CPU: every cell, configuration, traffic
mix, path and metric that ``BENCHMARK.json`` names is there, and the run
refuses to measure without a TPU."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import core  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_names_config_traffic_and_path(name):
    cell = core.cell(name)
    assert (core.BENCH_DIR / "paths" / f"{cell['traffic_data']['path']}.py").is_file()
    assert (core.BENCH_DIR / "work" / f"{cell['traffic_data']['path']}.py").is_file()
    assert cell["config_data"]["name"] == cell["config"]
    assert cell["chips"] in (1, 4)


@pytest.mark.parametrize("traffic", sorted(p.stem for p in (core.BENCH_DIR / "workloads").glob("*.json")))
def test_every_traffic_file_names_a_path(traffic):
    data = core.load_json(core.BENCH_DIR / "workloads" / f"{traffic}.json")
    assert (core.BENCH_DIR / "paths" / f"{data['path']}.py").is_file()


@pytest.mark.parametrize("metric", [m["name"] for m in SPEC["per_layer"]])
def test_every_per_layer_metric_has_a_reader(metric):
    assert callable(core.load_module("metrics", metric).read)


@pytest.mark.parametrize("metric", [m["name"] for m in SPEC["per_layer"]])
def test_reader_finds_nothing_in_no_trace(metric):
    ctx = {"trace": None, "work": {"ops": 0, "bytes": 1},
           "peaks": {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}}
    assert core.load_module("metrics", metric).read(ctx) is None


def test_end_to_end_metrics_include_setup():
    names = [m["name"] for m in SPEC["end_to_end"]]
    assert "setup_s" in names and len(names) >= 2


@pytest.mark.parametrize("conf", SPEC["configs"], ids=lambda c: c["name"])
def test_config_d_is_the_registry_models_parameter_count(conf):
    """d is the registry model's count plus each departure that the
    configuration lists."""
    import jax
    from repro.configs import registry
    from repro.models.model import init_params
    data = core.load_json(ROOT / conf["file"])
    arch = registry.get(data["registry"])
    shapes = jax.eval_shape(lambda k: init_params(arch, k), jax.random.PRNGKey(0))
    registry_d = sum(x.size for x in jax.tree.leaves(shapes))
    assert registry_d + sum(data["registry_departures"].values()) == data["d"]
    assert arch.n_layers == data["n_layers"] and arch.d_model == data["d_model"]
    assert arch.vocab == data["vocab"]


def _mamba2_count(c):
    """Mamba-2 (state-spaces/mamba2-130m): embedding tied to the head."""
    inner = c["ssm_expand"] * c["d_model"]
    conv_dim = inner + 2 * c["ssm_groups"] * c["ssm_state"]
    layer = (c["d_model"] * (inner + conv_dim + c["ssm_heads"])   # in_proj
             + conv_dim * (c["conv_width"] + 1)                    # conv1d, bias
             + 3 * c["ssm_heads"]                                  # dt_bias, A_log, D
             + inner                                               # gated norm
             + inner * c["d_model"]                                # out_proj
             + c["d_model"])                                       # pre-norm
    return c["n_layers"] * layer + c["vocab"] * c["d_model"] + c["d_model"]


def _whisper_count(c):
    """Whisper (openai/whisper-tiny): biased q, v, out; LayerNorms with
    biases; a two-matrix MLP with biases; head tied to the embedding."""
    m, ff = c["d_model"], c["d_ff"]
    attn = 4 * m * m + 3 * m
    mlp = 2 * m * ff + ff + m
    ln = 2 * m
    conv = (c["num_mel_bins"] * m * c["conv_width"] + m
            + m * m * c["conv_width"] + m)
    enc = conv + c["source_len"] * m + c["encoder_layers"] * (attn + mlp + 2 * ln) + ln
    dec = (c["vocab"] * m + c["decoder_len"] * m
           + c["n_layers"] * (2 * attn + mlp + 3 * ln) + ln)
    return enc + dec


@pytest.mark.parametrize("conf", SPEC["configs"], ids=lambda c: c["name"])
def test_config_d_is_the_published_parameter_count(conf):
    data = core.load_json(ROOT / conf["file"])
    count = {"mamba2-130m": _mamba2_count, "whisper-tiny": _whisper_count}[data["name"]]
    assert count(data) == data["d"]


def _run(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_without_a_tpu():
    p = _run(ROOT, {})
    assert p.returncode != 0
    assert not p.stdout.strip()
    assert "TPU" in p.stderr


def test_run_refuses_with_only_the_benchmark_files(tmp_path):
    shutil.copytree(core.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    p = _run(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert not p.stdout.strip()


def test_seed_key_keeps_every_bit():
    import jax
    keys = {tuple(jax.random.key_data(core.seed_key(s)).tolist())
            for s in (0, 1, 2 ** 31 + 5, 2 ** 32 + 5, 5, 2 ** 40)}
    assert len(keys) == 6
