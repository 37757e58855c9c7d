"""The reduction from a trace to the per-layer numbers, on small traces
whose numbers are counted by hand."""

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import trace  # noqa: E402

# times in ns; the window is [900, 3350): first "input" to last "block" end
SMALL = {
    "devices": {"/device:TPU:0": [
        ["early.0", "fusion", 0, 100],           # before the window
        ["fusion.1", "fusion", 1000, 500],
        ["sort.2", "sort", 1400, 300],          # overlaps fusion.1
        ["all-reduce.3", "all-reduce", 2000, 100],
        ["while.4", "while", 2900, 400],         # holds fusion.1: busy only
        ["fusion.1", "fusion", 3000, 200],
    ]},
    "host": [["input", 900, 100], ["round", 1000, 50], ["block", 1050, 2300]],
}


def test_small_trace_by_hand():
    r = trace.reduce(SMALL, rounds=2)
    assert r["window_s"] == pytest.approx(2450e-9)
    # busy: [1000, 1700) + [2000, 2100) + [2900, 3300)
    assert r["fullest_busy_s"] == pytest.approx(1200e-9)
    assert r["busy_s"] == pytest.approx(1200e-9)
    assert r["select_s"] == pytest.approx(300e-9)
    assert r["collective_s"] == pytest.approx(100e-9)
    assert r["device_ops"] == [["fusion.1", pytest.approx(700e-9)],
                               ["sort.2", pytest.approx(300e-9)],
                               ["all-reduce.3", pytest.approx(100e-9)]]
    # gaps: [900,1000) input; [1700,2000), [2100,2900), [3300,3350) block
    assert r["idle_gaps"] == [["block", pytest.approx(800e-9)],
                              ["block", pytest.approx(300e-9)],
                              ["input", pytest.approx(100e-9)],
                              ["block", pytest.approx(50e-9)]]


def test_fullest_of_two_devices():
    tr = json.loads(json.dumps(SMALL))
    tr["devices"]["/device:TPU:1"] = [["fusion.9", "fusion", 950, 2000]]
    r = trace.reduce(tr, rounds=1)
    assert r["device"] == "/device:TPU:1"
    assert r["fullest_busy_s"] == pytest.approx(2000e-9)
    assert r["busy_s"] == pytest.approx(1600e-9)   # mean over the chips
    assert r["select_s"] == 0


@pytest.mark.parametrize("tr", [
    {"devices": {}, "host": SMALL["host"]},
    {"devices": SMALL["devices"], "host": []},
    {"devices": {"/device:TPU:0": []}, "host": SMALL["host"]},
])
def test_nothing_to_read(tr):
    assert trace.reduce(tr, rounds=1) is None


@pytest.mark.parametrize("text,name,want", [
    ("%sort.49 = (f32[1,124945536]{1,0:T(1,128)}, s32[1,124945536]{1,0:T(1,128)}) "
     "sort(f32[1,124945536]{1,0:T(1,128)} %bitcast.100, s32[1,124945536]{1,0:T(1,128)} "
     "%bitcast.101), dimensions={1}, to_apply=%compare-greater-than.2.clone", "sort.49", "sort"),
    ("%fusion.96 = s32[6447024]{0:T(1024)S(1)} fusion(s32[128940480]{0:T(1024)} "
     "%get-tuple-element.852), kind=kCustom, calls=%fused_computation.clone", "fusion.96", "fusion"),
    ("%all-reduce.2 = s32[4]{0} all-reduce(s32[4]{0} %x), to_apply=%add", "all-reduce.2", "all-reduce"),
    ("%while.31 = (s32[]{:T(128)}, f32[8,16]{1,0:T(8,128)}) while((s32[]{:T(128)}, "
     "f32[8,16]{1,0:T(8,128)}) %tuple.235), condition=%c, body=%b", "while.31", "while"),
    ("copy.4", "copy.4", "copy"),
])
def test_opcode_and_name_from_hlo_text(text, name, want):
    assert trace.opcode(text) == want
    assert trace.op_name(text) == name


def test_recorded_v5e_round():
    """One stream topk round (N = 8, d = 2**24) traced on a v5e chip: the
    host spans and the device ops of 5 ms or more, shifted so that the
    window starts at 1000 ns. The numbers were counted apart from the
    reduction, on a nanosecond timeline."""
    tr = json.loads((ROOT / "bench/tests/data/v5e_topk_round.json").read_text())
    r = trace.reduce(tr, rounds=1)
    assert r["window_s"] == pytest.approx(2233805654e-9, abs=1e-12)
    assert r["fullest_busy_s"] == pytest.approx(2210926472e-9, abs=1e-12)
    assert r["select_s"] == pytest.approx(1882041873e-9, abs=1e-12)
    assert r["collective_s"] == 0
    assert r["device_ops"][0][0] == "sort.49"
    assert sum(t for _, t in r["idle_gaps"]) <= r["window_s"] - r["fullest_busy_s"] + 1e-12
