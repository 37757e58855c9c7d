"""Required-work counts and the table of peaks."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import core  # noqa: E402


def test_round_count_is_the_hand_count():
    work = core.load_module("work", "round").required(2, 3)
    # read g [2, 3] and r [2, 3], write r' [2, 3] and delta [3], f32
    assert work == {"ops": 0, "bytes": (6 + 6 + 6 + 3) * 4}


ROUND_TRAFFIC = sorted(p.stem for p in (core.BENCH_DIR / "workloads").glob("*.json")
                       if core.load_json(p)["path"] == "round")


@pytest.mark.parametrize("traffic", ROUND_TRAFFIC)
def test_round_count_is_below_what_the_round_reads_and_writes(traffic):
    import jax
    import jax.numpy as jnp
    from bench.paths import round as rp
    cell = core.make_cell("mamba2-130m", traffic)
    n, d = cell["traffic_data"]["clients"], 4096
    step = rp.program_round(cell["traffic_data"])
    g = jax.ShapeDtypeStruct((n, d), jnp.float32)
    key = jax.eval_shape(lambda: jax.random.key(0))
    outs = jax.eval_shape(step, g, key)
    provable = 2 * g.size * 4 + sum(o.size * o.dtype.itemsize for o in outs)
    work = core.load_module("work", "round").required(n, d)
    assert 0 < work["bytes"] <= provable


def test_peaks_of_a_v5e_chip():
    p = core.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert p["int8_ops_per_s"] == 393e12 and p["hbm_bytes"] == 16e9


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "", "tpu v5 lite"])
def test_unknown_device_kind_raises(kind):
    with pytest.raises(KeyError):
        core.peaks(kind)


def test_round_mfu_reads_the_memory_bound():
    mfu = core.load_module("metrics", "round_mfu")
    ctx = {"trace": {"rounds": 4, "window_s": 2.0},
           "work": {"ops": 0, "bytes": 819e9 * 0.05},
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    # least time 0.05 s over 0.5 s a round
    assert mfu.read(ctx) == pytest.approx(10.0)
