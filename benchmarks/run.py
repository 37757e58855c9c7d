"""Benchmark harness entry point — one section per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--only fig2,tables,...] [--smoke]

Prints ``name,value,derived`` CSV rows (see each module's docstring for the
paper artifact it reproduces).  A section that raises is recorded as a
``<name>/ERROR`` row, the remaining sections still run, and the command
exits 1.  ``--smoke`` runs every section on a tiny
budget (seconds per section; sections that normally write tracked
``BENCH_*.json`` files write to a temp path instead) — the registry test
exercises exactly this mode.
"""

from __future__ import annotations

import argparse
import sys
import time

from repro.launch.cache import enable_compile_cache

from . import (accuracy_vs_time, aggregation_ops, aggregation_round,
               async_throughput, compression_error, dataplane, faults,
               kernel_micro, noniid, obs, robust, roofline, sweep, traffic,
               vote_threshold)
from .common import emit

SECTIONS = {
    "fig2": accuracy_vs_time.run,       # accuracy vs wall-clock
    "tables": traffic.run,              # Tables I/II traffic
    "fig3": noniid.run,                 # non-IID beta sweep
    "fig4": vote_threshold.run,         # a x N sweep
    "prop1": compression_error.run,     # gamma bound + Cor.1
    "motivation": aggregation_ops.run,  # Sec III-B example
    "kernels": kernel_micro.run,        # Pallas kernel micro
    "aggregation": aggregation_round.run,  # round-plan engine vs seed
    "dataplane": dataplane.run,         # packet dataplane: loss x participation
    "faults": faults.run,               # chaos dataplane: faults + recovery
    "async": async_throughput.run,      # async close: identity + throughput
    "robust": robust.run,               # Byzantine attacks x defenses
    "sweep": sweep.run,                 # fleet runner vs sequential loop
    "roofline": roofline.run,           # dry-run roofline table
    "obs": obs.run,                     # telemetry: trace audit + overhead
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated section names " + str(list(SECTIONS)))
    ap.add_argument("--smoke", action="store_true",
                    help="tiny-budget run of every section (CI / registry test)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    names = args.only.split(",") if args.only else list(SECTIONS)
    print("name,value,derived")
    failed = []
    for name in names:
        t0 = time.time()
        try:
            rows = SECTIONS[name](smoke=args.smoke)
        except Exception as e:  # run the other sections, then fail the run
            rows = [(f"{name}/ERROR", type(e).__name__, str(e)[:120])]
            failed.append(name)
        emit(rows)
        print(f"# section {name} done in {time.time() - t0:.1f}s", file=sys.stderr)
    if failed:
        print(f"# sections failed: {','.join(failed)}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
