"""``correct`` on the CPU at a size a test run holds: the timed path
passes, and the control and each planted fault fail.

Each case drives the rest of a run (set-up, a short window, the check)
with the harness's look for a chip skipped and the round that the
variant names in the program's place (``bench/control.py``). d = 2**18
keeps the program's sampled top-k selection on its fast path, as at the
cells' widths.
"""

import sys
from functools import partial
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import control, core  # noqa: E402
from bench import run as brun  # noqa: E402
from bench.paths import round as rp  # noqa: E402

SMALL_D = 2 ** 18
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# the benchmark's mix, and the topk mix on which the program's tie fault shows
CASES = [(traffic, variant)
         for traffic in ("n8.topk", "n8.threshold")
         for variant in control.VARIANTS]


def _small(config, traffic):
    cell = core.make_cell(config, traffic)
    cell["config_data"] = dict(cell["config_data"], d=SMALL_D)
    return cell


@pytest.mark.parametrize("traffic,variant", CASES)
def test_only_the_program_is_correct(monkeypatch, traffic, variant):
    import jax
    monkeypatch.setattr(core, "peaks", lambda kind: PEAKS)
    cell = _small("mamba2-130m", traffic)
    step = control.variant_round(variant, cell["traffic_data"])
    res = brun.measure(cell, 2 ** 32 + 77, 0.5, False, jax.devices(),
                       run_cls=partial(rp.Run, round_fn=step))
    assert res["correct"] is (variant == "program"), res["checks"]
    assert list(res)[-1] == "checks"
    assert res["attempted"] >= 1


@pytest.mark.parametrize("traffic", ["n32.threshold"])
def test_many_clients_are_correct(monkeypatch, traffic):
    """32 clients, traced: correct, and nothing compiles in the window."""
    import jax
    monkeypatch.setattr(core, "peaks", lambda kind: PEAKS)
    cell = _small("whisper-tiny", traffic)
    cell["name"] = "round.whisper-tiny.n32.threshold"   # the cell's metrics
    res = brun.measure(cell, 5, 0.3, True, jax.devices())
    assert res["correct"], res["checks"]
    assert res["metrics"]["compiles_in_window"]["value"] == 0
