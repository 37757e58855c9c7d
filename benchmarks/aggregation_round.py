"""End-to-end aggregation wall-clock: the round-plan engines vs the
kept-alive seed path, measured in the same run.

Grid: d in {1e5, 1e6} x N in {8, 32} x both selection-mode pairs
(topk/topk — paper-faithful — and threshold/block — the sort-free
billion-parameter mode) for the monolithic engine, plus the streaming
chunk-scanned engine (DESIGN.md §12) at d = 1e6 and at **d = 1e7** — a
round size whose monolithic [N, d] temporaries don't fit this box's
working set, so it is tracked engine-only.  Engine outputs are checked
**bit-identical** to the seed on every compared cell.

Timing interleaves seed/engine reps and reports median seconds per
candidate plus a paired-ratio-median speedup
(``common.{interleaved_times,paired_ratio_median}``): on this 2-core box
back-to-back means drift 1.5-2x run to run, which used to make the
threshold/block speedups look like regressions.  On the CPU each cell
runs in a spawned subprocess so its ``peak_rss_mb`` — the memory story of
the streaming engine — is its own high-water mark, not the grid's; on an
accelerator every cell runs in this process, which holds the chip.

Writes ``BENCH_aggregation.json`` at the repo root so the perf trajectory
is tracked; emits the usual CSV rows for ``benchmarks.run``.

  PYTHONPATH=src python -m benchmarks.aggregation_round [--no-compare-seed]
                                                        [--no-rss] [--out P]
"""

from __future__ import annotations

import argparse
import json
import os
import statistics

import jax
import jax.numpy as jnp

from .common import (emit, interleaved_times, paired_ratio_median,
                     smoke_out_path)

OUT_PATH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "BENCH_aggregation.json")

GRID = [(100_000, 8), (100_000, 32), (1_000_000, 8), (1_000_000, 32)]
MODES = [("topk", "topk"), ("threshold", "block")]
# streaming-engine cells: the 1e6 overlap cells (still seed-compared, so
# bit-identity stays pinned at benchmark scale) and the 1e7 scale cell.
STREAM_GRID = [(1_000_000, 8, "topk", "topk", True),
               (1_000_000, 8, "threshold", "block", True),
               (10_000_000, 8, "topk", "topk", False)]
REPS = 5

# The sharded-engine scale cell (DESIGN.md §16).  The headline is *per-
# device peak memory* at d ~ 1e8 — read from XLA's per-device
# ``memory_analysis()`` of the compiled round, so the cell never has to
# materialize 1e8-sized buffers — compared against the streaming engine
# compiled for a single device in the same process.  The width is mesh-
# and block-aligned (8 devices x 4096-blocks) so the engine is measured
# without pad/slice copies, exactly as it runs at scale.  Wall-clock is
# timed at a size both engines execute comfortably, and bit-identity vs
# ``aggregate_stack`` is checked at a size the monolithic oracle holds.
SHARD_DEVICES = 8
SHARD_D = 8 * 4096 * 3052          # 100_007_936 ~ 1e8, pad-free on the mesh
SHARD_TIMING_D = 8 * 4096 * 305    # ~1e7: the stream scale-cell size
SHARD_BITIDENT_D = 8 * 4096 * 30   # ~1e6: oracle-comparable


def bench_sharded_cell(*, d: int = SHARD_D, timing_d: int = SHARD_TIMING_D,
                       bitident_d: int = SHARD_BITIDENT_D,
                       devices: int = SHARD_DEVICES, reps: int = 3) -> dict:
    """The sharded-engine scale cell.  Requires ``devices`` visible jax
    devices — run through ``_sharded_measured_cell``, which forces the
    host-platform device count in a spawned child."""
    from repro.core.engines import EngineSpec
    from repro.core.fediac import (FediACConfig, aggregate_round,
                                   aggregate_stack)

    assert len(jax.devices()) >= devices, (len(jax.devices()), devices)
    vote_mode, compact_mode = "threshold", "block"
    base_cfg = FediACConfig(vote_mode=vote_mode, compact_mode=compact_mode)
    shard_cfg = FediACConfig(
        vote_mode=vote_mode, compact_mode=compact_mode,
        engine=EngineSpec(name="sharded", devices=devices))
    stream_cfg = FediACConfig(vote_mode=vote_mode, compact_mode=compact_mode,
                              engine="stream")
    n = 8
    key = jax.random.PRNGKey(0)

    def round_fn(cfg):
        return jax.jit(lambda u, k: aggregate_round(u, cfg, k)[:3])

    def per_device_peak_mb(cfg, dd: int) -> float:
        m = round_fn(cfg).lower(
            jax.ShapeDtypeStruct((n, dd), jnp.float32),
            jax.ShapeDtypeStruct(key.shape, key.dtype)
        ).compile().memory_analysis()
        return round((m.temp_size_in_bytes + m.argument_size_in_bytes +
                      m.output_size_in_bytes - m.alias_size_in_bytes)
                     / 2 ** 20, 1)

    per_dev = per_device_peak_mb(shard_cfg, d)
    stream_mb = per_device_peak_mb(stream_cfg, d)

    ub = jax.block_until_ready(
        jax.random.normal(jax.random.PRNGKey(1), (n, bitident_d)) ** 3)
    ref = aggregate_stack(ub, base_cfg, key)
    got = aggregate_round(ub, shard_cfg, key)
    identical = (all(bool(jnp.all(a == b))
                     for a, b in zip(ref[:3], got[:3]))
                 and ref[3] == got[3])
    del ref, got, ub

    ut = jax.block_until_ready(
        jax.random.normal(jax.random.PRNGKey(2), (n, timing_d)) ** 3)
    fns = {}
    for label, cfg in (("engine", shard_cfg), ("stream", stream_cfg)):
        fn = round_fn(cfg)
        jax.block_until_ready(fn(ut, key))  # compile + warm
        fns[label] = (lambda fn=fn: jax.block_until_ready(fn(ut, key)))
    times = interleaved_times(fns, reps=reps)
    return {
        "d": d, "n_clients": n, "vote_mode": vote_mode,
        "compact_mode": compact_mode, "engine": "sharded",
        "devices": devices, "reps": reps,
        "engine_s": round(statistics.median(times["engine"]), 4),
        "stream_s": round(statistics.median(times["stream"]), 4),
        # paired ratio vs the stream engine at timing_d; fake host-platform
        # devices share the machine's cores, so this is a fidelity record
        # (gated by wide band), not a speedup claim.
        "vs_stream": round(paired_ratio_median(times["stream"],
                                               times["engine"]), 3),
        "timing_d": timing_d, "bitident_d": bitident_d,
        "bit_identical": identical,
        "per_device_peak_mb": per_dev, "stream_peak_mb": stream_mb,
        "mem_ratio": round(per_dev / stream_mb, 4),
    }


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


def _sharded_measured_cell(**kwargs) -> dict:
    """On the CPU the sharded cell runs in its own spawned process: the
    fake device count is forced via ``XLA_FLAGS``, which only takes effect
    at jax init, and ``run_isolated``'s child inherits the patched env.
    On an accelerator this process holds the chips, so the cell runs here,
    over the devices it has."""
    if not _on_cpu():
        kwargs.setdefault("devices", len(jax.devices()))
        return bench_sharded_cell(**kwargs)
    from .memprof import run_isolated
    devices = kwargs.get("devices", SHARD_DEVICES)
    flag = f"--xla_force_host_platform_device_count={devices}"
    old = os.environ.get("XLA_FLAGS")
    os.environ["XLA_FLAGS"] = f"{old} {flag}" if old else flag
    try:
        cell, peak = run_isolated(
            "benchmarks.aggregation_round:bench_sharded_cell", **kwargs)
    finally:
        if old is None:
            os.environ.pop("XLA_FLAGS", None)
        else:
            os.environ["XLA_FLAGS"] = old
    cell["peak_rss_mb"] = peak
    return cell


def bench_cell(d: int, n: int, vote_mode: str, compact_mode: str,
               *, engine: str = "monolithic", stream_chunk: int = 0,
               compare_seed: bool = True, reps: int = REPS) -> dict:
    from repro.core.fediac import FediACConfig, aggregate_round
    from repro.core.seed_ref import aggregate_stack_seed

    cfg = FediACConfig(vote_mode=vote_mode, compact_mode=compact_mode,
                       engine=engine, stream_chunk=stream_chunk)
    key = jax.random.PRNGKey(0)
    u = jax.block_until_ready(
        jax.random.normal(jax.random.PRNGKey(1), (n, d)) ** 3)
    # no donation here: a timing loop must keep `u` alive across reps, so
    # aliasing could never kick in anyway (the donation contract is pinned
    # by tests/test_stream_engine.py instead).
    engine_fn = jax.jit(lambda u, k: aggregate_round(u, cfg, k)[:3])
    seed_fn = jax.jit(lambda u, k: aggregate_stack_seed(u, cfg, k))

    out_e = jax.block_until_ready(engine_fn(u, key))  # compile + warm
    fns = {"engine": lambda: jax.block_until_ready(engine_fn(u, key))}
    identical = True
    if compare_seed:
        out_s = jax.block_until_ready(seed_fn(u, key))
        identical = all(bool(jnp.all(a == b)) for a, b in zip(out_e, out_s))
        del out_s
        fns["seed"] = lambda: jax.block_until_ready(seed_fn(u, key))
    # drop the warmup outputs before timing: holding residuals [N, d] alive
    # through the reps would inflate peak_rss_mb (the scale cell's headline).
    del out_e
    times = interleaved_times(fns, reps=reps)
    cell = {
        "d": d, "n_clients": n, "vote_mode": vote_mode,
        "compact_mode": compact_mode, "engine": engine, "reps": reps,
        "engine_s": round(statistics.median(times["engine"]), 4),
    }
    if compare_seed:
        cell["seed_s"] = round(statistics.median(times["seed"]), 4)
        # per-rep paired ratio: a machine-noise burst inflates the seed and
        # engine rep it spans together, so the ratio barely moves.
        cell["speedup"] = round(paired_ratio_median(times["seed"],
                                                    times["engine"]), 3)
        cell["bit_identical"] = identical
    return cell


def _measured_cell(*args, rss: bool, **kwargs) -> dict:
    """One cell, in its own process when a CPU peak-RSS reading is wanted.
    On an accelerator no child is spawned: this process holds the chip."""
    if not rss or not _on_cpu():
        return bench_cell(*args, **kwargs)
    from .memprof import run_isolated
    cell, peak = run_isolated("benchmarks.aggregation_round:bench_cell",
                              *args, **kwargs)
    cell["peak_rss_mb"] = peak
    return cell


def run(*, compare_seed: bool = True, smoke: bool = False, rss: bool = True,
        out_path: str = OUT_PATH):
    if smoke:
        out_path = smoke_out_path(out_path, OUT_PATH,
                                  "BENCH_aggregation.smoke.json")
    grid = GRID[:1] if smoke else GRID
    modes = MODES[:1] if smoke else MODES
    stream_grid = ([(100_000, 8, "topk", "topk", True)] if smoke
                   else STREAM_GRID)
    reps = 2 if smoke else REPS
    rss = rss and not smoke
    cells, rows = [], []
    for vote_mode, compact_mode in modes:
        for d, n in grid:
            cells.append(_measured_cell(d, n, vote_mode, compact_mode,
                                        rss=rss, compare_seed=compare_seed,
                                        reps=reps))
    for d, n, vote_mode, compact_mode, vs_seed in stream_grid:
        cells.append(_measured_cell(d, n, vote_mode, compact_mode, rss=rss,
                                    engine="stream",
                                    compare_seed=compare_seed and vs_seed,
                                    reps=min(reps, 3) if d > 2_000_000
                                    else reps))
    shard_kwargs = (dict(d=SHARD_BITIDENT_D, timing_d=4 * 32_768,
                         bitident_d=4 * 32_768, reps=2) if smoke else {})
    cells.append(_sharded_measured_cell(**shard_kwargs))
    for cell in cells:
        tag = (f"agg/{cell['engine']}/{cell['vote_mode']}-"
               f"{cell['compact_mode']}/d{cell['d']}/n{cell['n_clients']}")
        extra = (f"_rss={cell['peak_rss_mb']}MB" if "peak_rss_mb" in cell
                 else "")
        if "mem_ratio" in cell:
            rows.append((tag, cell["mem_ratio"],
                         f"perdev={cell['per_device_peak_mb']}MB_stream="
                         f"{cell['stream_peak_mb']}MB_vs_stream="
                         f"{cell['vs_stream']}_bitident="
                         f"{cell['bit_identical']}{extra}"))
        elif "speedup" in cell:
            rows.append((tag, cell["speedup"],
                         f"engine={cell['engine_s']}s_seed={cell['seed_s']}s_"
                         f"bitident={cell['bit_identical']}{extra}"))
        else:
            rows.append((tag, cell["engine_s"], f"engine_only{extra}"))
    payload = {
        "benchmark": "aggregation_round",
        "backend": jax.default_backend(),
        "unit": "seconds_per_round",
        "cells": cells,
    }
    with open(out_path, "w") as fh:
        json.dump(payload, fh, indent=2)
        fh.write("\n")
    rows.append(("agg/json", out_path, "written"))
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--no-compare-seed", dest="compare_seed",
                    action="store_false", default=True,
                    help="time only the engines (skip the seed reference)")
    ap.add_argument("--no-rss", dest="rss", action="store_false",
                    default=True,
                    help="run cells in-process (no peak_rss_mb records)")
    ap.add_argument("--smoke", action="store_true",
                    help="small cells, temp output (CI)")
    ap.add_argument("--out", default=OUT_PATH)
    args = ap.parse_args(argv)
    emit(run(compare_seed=args.compare_seed, smoke=args.smoke, rss=args.rss,
             out_path=args.out))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
