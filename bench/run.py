#!/usr/bin/env python3
"""The FediAC benchmark: one cell of ``BENCHMARK.json`` on the chips.

  python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A cell names a configuration (``bench/configs/<config>.json``) and a
traffic mix (``bench/workloads/<traffic>.json``); the mix names its path
(``bench/paths/<path>.py``), which sets up the data from the seed,
compiles and warms every program, runs one round per call and checks the
outputs against the plain reference. The per-layer metrics are readers
``bench/metrics/<name>.py``. Adding a cell or a metric adds files; this
script stays as it is.

Set-up runs from process start to the first timed round. The window runs
whole rounds until ``--seconds`` have passed. ``--trace 0`` reports the
cell's end-to-end metrics; ``--trace 1`` traces the window with JAX's
profiler and reports the per-layer metrics. The last line of standard
output is one JSON object; the numbers compared for ``correct`` are the
last lines of standard error and the last key of that object. With no
TPU, or fewer chips than the cell asks for, it exits non-zero and prints
no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import core  # noqa: E402
from bench import trace as tracing  # noqa: E402


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def per_layer_names(spec: dict, cell: dict) -> list:
    """The per-layer metrics this cell reports: those that list it, or,
    with no list, those whose end-to-end metric it reports."""
    e2e = {m["name"] for m in spec["end_to_end"]
           if cell["name"] in m.get("workloads", [cell["name"]])}
    return [m["name"] for m in spec["per_layer"]
            if (cell["name"] in m["workloads"] if "workloads" in m
                else m["moves"] in e2e)]


def measure(cell: dict, seed: int, seconds: float, trace: bool, devs,
            run_cls=None) -> dict:
    """Set-up, window and check of one cell; the result object."""
    spec = core.benchmark()
    path = core.load_module("paths", cell["traffic_data"]["path"])
    t_run = time.perf_counter()
    run = (run_cls or path.Run)(cell, seed, devs)
    setup_s = time.perf_counter() - T_START
    print(f"# setup_s={setup_s!r} before_data_s={t_run - T_START!r} "
          f"phases={getattr(run, 'setup_phases', None)!r}", file=sys.stderr, flush=True)

    traced = {}
    times = []
    with core.CompileCounter() as counter, \
            (tracing.captured(traced) if trace else contextlib.nullcontext()):
        t0 = time.perf_counter()
        while True:
            run.round()
            times.append(time.perf_counter())
            if times[-1] - t0 >= seconds:
                break
    elapsed = times[-1] - t0
    peak = core.peak_bytes(devs)
    rounds = len(times)
    per_round = [b - a for a, b in zip([t0] + times[:-1], times)]
    print(f"# rounds={rounds} window_s={elapsed!r} round_s={per_round!r} "
          f"median_round_s={sorted(per_round)[rounds // 2]!r}",
          file=sys.stderr, flush=True)

    device = core.device_info(devs, peak)
    work = run.work()
    peaks = core.peaks(device["kind"])
    metrics, breakdown = {}, None
    if trace:
        red = tracing.reduce(traced["trace"], rounds) if traced.get("trace") else None
        ctx = {"trace": red, "work": work, "peaks": peaks,
               "compiles_in_window": counter.count}
        units = {m["name"]: m["unit"] for m in spec["per_layer"]}
        for name in per_layer_names(spec, cell):
            v = core.load_module("metrics", name).read(ctx)
            if v is not None:
                metrics[name] = {"value": v, "unit": units[name]}
        if red:
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            breakdown = {"device_ops": red["device_ops"],
                         "idle_gaps": red["idle_gaps"]}
        bound = ("memory" if work["bytes"] / peaks["hbm_bytes_per_s"]
                 >= work["ops"] / peaks["bf16_flops_per_s"] else "compute")
        print(f"# round_mfu bound: {bound}", file=sys.stderr, flush=True)
    else:
        values = {"round_ms": 1e3 * elapsed / rounds,
                  "peak_hbm_gib": peak / 2 ** 30, "setup_s": setup_s}
        for m in spec["end_to_end"]:
            if cell["name"] in m.get("workloads", [cell["name"]]):
                metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}

    checks = run.check()
    print(f"# check_s={getattr(run, 'check_s', None)!r}", file=sys.stderr, flush=True)
    correct = all(v <= lim for _, v, lim in checks)
    result = {"correct": correct, "attempted": rounds,
              "failed": 0 if correct else rounds, "metrics": metrics,
              "device": device}
    if breakdown:
        result["breakdown"] = breakdown
    result["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}
    for n, v, lim in checks:
        print(f"check {n} = {v} (limit {lim})", file=sys.stderr, flush=True)
    return result


def main(argv=None) -> int:
    args = parse(argv)
    cell = core.cell(args.workload)
    devs = core.require_chips(int(cell["chips"]))
    core.enable_compile_cache()
    result = measure(cell, args.seed, args.seconds, bool(args.trace), devs)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
