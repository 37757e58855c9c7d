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


@pytest.mark.parametrize("conf", SPEC["configs"], ids=lambda c: c["name"])
def test_config_d_is_the_published_parameter_count(conf):
    """d is the count that ``bench/configs/<name>.py`` works out from the
    file's own numbers."""
    data = core.load_json(ROOT / conf["file"])
    path = core.BENCH_DIR / "configs" / f"{data['name']}.py"
    assert path.is_file(), (f"configuration {data['name']!r} has no count "
                            f"module: {path} is missing")
    assert core.load_module("configs", data["name"]).count(data) == data["d"]


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


ROOM_CONFIG = "room-probe"
ROOM_COUNT = '''
def count(c):
    inner = c["ssm_expand"] * c["d_model"]
    conv_dim = inner + 2 * c["ssm_groups"] * c["ssm_state"]
    layer = (c["d_model"] * (inner + conv_dim + c["ssm_heads"])
             + conv_dim * (c["conv_width"] + 1) + 3 * c["ssm_heads"] + inner
             + inner * c["d_model"] + c["d_model"])
    return c["n_layers"] * layer + c["vocab"] * c["d_model"] + c["d_model"]
'''


def test_a_configuration_and_a_mix_join_by_files_alone(tmp_path):
    """In a copy of the benchmark, a new configuration (its file and its
    count module), a traffic mix of an existing path and a cell of both
    pass the harness's own checks, with no file of the copy edited."""
    shutil.copytree(core.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*") if p.is_file()}
    conf = dict(core.load_json(core.BENCH_DIR / "configs" / "mamba2-130m.json"),
                name=ROOM_CONFIG)
    (tmp_path / "bench/configs" / f"{ROOM_CONFIG}.json").write_text(json.dumps(conf))
    (tmp_path / "bench/configs" / f"{ROOM_CONFIG}.py").write_text(ROOM_COUNT)
    mix = dict(core.load_json(core.BENCH_DIR / "workloads" / "n8.threshold.json"),
               clients=4, a=1)
    (tmp_path / "bench/workloads/n4.room.json").write_text(json.dumps(mix))
    spec = json.loads(json.dumps(SPEC))
    spec["configs"].append({"name": ROOM_CONFIG, "source": "https://example.org/room",
                            "file": f"bench/configs/{ROOM_CONFIG}.json",
                            "reduced": [], "why": "a throwaway configuration"})
    spec["workloads"].append({"name": f"round.{ROOM_CONFIG}.n4", "config": ROOM_CONFIG,
                              "traffic": "n4.room", "chips": 1, "why": "a throwaway cell"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    p = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-rA", "-p", "no:cacheprovider",
         "-p", "no:randomly", "bench/tests/test_bench_harness.py",
         "bench/tests/test_bench_work.py", "-k",
         "cell_names or traffic_file or parameter_count or below_what"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stdout[-4000:] + p.stderr[-2000:]
    passed = [line for line in p.stdout.splitlines() if line.startswith("PASSED")]
    for case in (f"round.{ROOM_CONFIG}.n4", "traffic_file_names_a_path[n4.room]",
                 f"registry_models_parameter_count[{ROOM_CONFIG}]",
                 f"published_parameter_count[{ROOM_CONFIG}]", "writes[n4.room]"):
        assert any(case in line for line in passed), (case, passed)
    assert all(p.read_bytes() == b for p, b in before.items())
