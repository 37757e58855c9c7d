#!/usr/bin/env python3
"""Readings that set the limits of ``correct``: the program's own on many
seeds (the lower reading), the control's and the planted faults' (the
upper one). Not run by the benchmark's runs.

  python3 bench/control.py --workload <name> --variant <variant> \\
      --seeds 1,2,3 --seconds <s>

runs the cell's set-up, a window of ``--seconds`` and the check once per
seed, in one process, with the round that the variant names in the
program's place, and prints one JSON line per seed with the numbers
compared. Variants:

- ``program``: the timed path as it is;
- ``control``: the plain reference computed in bfloat16, the precision
  below the float32 the configuration states, in the program's place;
- ``state_unchanged``: the program's delta and counts, but its input
  returned as the residual: a round that leaves its state as it was;
- ``half_batch``: the program over the first half of the clients, the
  mean taken over those, the other half's uploads returned unchanged;
- ``altered_answer``: the program with one coordinate of delta changed in
  its last bit, where the round produces it.
"""

from __future__ import annotations

import argparse
import json
import sys
from functools import partial
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import core  # noqa: E402


def variant_round(variant: str, traffic: dict):
    """``(u, key) -> (delta, residual, counts)`` for ``variant``."""
    import jax
    import jax.numpy as jnp
    from bench.paths import round as rp
    from bench.reference import fediac_round as ref
    if variant == "program":
        return rp.program_round(traffic)
    if variant == "control":
        p = ref.params(traffic, work_dtype="bfloat16")
        return lambda u, key: ref.round_step(u, key, p)
    cfg = rp.fediac_config(traffic)
    from repro.api import aggregate_round
    if variant == "state_unchanged":
        def f(u, key):
            delta, _, counts = aggregate_round(u, cfg, key)[:3]
            return delta, u, counts
    elif variant == "half_batch":
        def f(u, key):
            h = u.shape[0] // 2
            delta, res, counts = aggregate_round(u[:h], cfg, key)[:3]
            return delta, jnp.concatenate([res, u[h:]]), counts
    elif variant == "altered_answer":
        def f(u, key):
            delta, res, counts = aggregate_round(u, cfg, key)[:3]
            bits = jax.lax.bitcast_convert_type(delta[0], jnp.uint32) ^ 1
            return delta.at[0].set(jax.lax.bitcast_convert_type(bits, jnp.float32)), res, counts
    else:
        raise ValueError(f"unknown variant {variant!r}")
    return jax.jit(f)


VARIANTS = ("program", "control", "state_unchanged", "half_batch", "altered_answer")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--variant", choices=VARIANTS, required=True)
    p.add_argument("--seeds", required=True, help="comma-separated")
    p.add_argument("--seconds", type=float, required=True)
    args = p.parse_args(argv)
    from bench import run as brun
    cell = core.cell(args.workload)
    devs = core.require_chips(int(cell["chips"]))
    core.enable_compile_cache()
    path = core.load_module("paths", cell["traffic_data"]["path"])
    step = variant_round(args.variant, cell["traffic_data"])
    for seed in (int(s) for s in args.seeds.split(",")):
        res = brun.measure(cell, seed, args.seconds, False, devs,
                           run_cls=partial(path.Run, round_fn=step))
        print(json.dumps({"workload": args.workload, "variant": args.variant,
                          "seed": seed, "correct": res["correct"],
                          "rounds": res["attempted"], "checks": res["checks"],
                          "round_ms": res["metrics"]["round_ms"]["value"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
