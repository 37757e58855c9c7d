#!/usr/bin/env python3
"""Smoke run of the FediAC system on a TPU, through the entry points a
user calls: the Pallas kernels of ``repro.kernels.ops``, one stacked round
through ``repro.api.aggregate_round`` on each engine, and the FL loop
through ``repro.api.run_federated`` over the packet transport.

  python3 chip_smoke.py               # one chip
  python3 chip_smoke.py --four-chips  # a 2x2 host: the sharded engine at
                                      # d ~ 1e8 against the stream engine
                                      # on one chip, and the qwen3-0.6b
                                      # trainer (python -m repro.launch.train)

Every check prints one line: its name, seconds (compile apart where the
program is compiled ahead), the device's peak memory so far and what was
checked.  All phases run; if any check failed the script exits 1 and does
not print the last line.  Otherwise the last line is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``.  There is no CPU
path: without a TPU the first check fails.  One process drives every
chip; nothing here starts a child process.

This is a smoke run, not a benchmark: each time is one cold reading.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import math
import sys
import time
import traceback
from dataclasses import replace
from functools import partial
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.api import (EngineSpec, FediACConfig, ScenarioSpec,  # noqa: E402
                       aggregate_round, run_federated)
from repro.kernels import ops, ref  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402

D_KERNEL = 2 ** 24            # kernel and round width (16384 x 1024 lanes)
N_COUNT = 32                  # clients in the popcount kernel check
N_ROUND = 8                   # clients in every round
STREAM_CHUNK = 2 ** 22
# ~1e8: the largest tracked aggregation cell (BENCH_aggregation.json's
# sharded cell), block- and mesh-aligned for 1, 2, 4 or 8 devices.
D_BIG = 8 * 4096 * 3052
MODES = (("topk", "topk"), ("threshold", "block"))
# examples/fl_lossy_network.py's synthetic non-IID task, packet transport
FL_TASK = dict(algorithm="fediac", a=2, bits=12, n_clients=10, rounds=5,
               local_steps=3, dist="noniid", beta=0.5, data_n=6000,
               data_dim=32, data_classes=10, test_frac=0.2,
               transport="packet")
TRAIN_ARGV = ["--arch", "qwen3-0.6b", "--aggregator", "fediac",
              "--steps", "3"]


class Report:
    """Prints one line per check and remembers the ones that failed."""

    def __init__(self):
        self.failed = []

    def line(self, name, ok, check, *, seconds=None, compile_s=None):
        parts = [f"{name:<34s}", "ok  " if ok else "FAIL"]
        if compile_s is not None:
            parts.append(f"compile={compile_s:.2f}s")
        if seconds is not None:
            parts.append(f"run={seconds:.3f}s")
        parts.append(f"peak={peak_mib():.0f}MiB")
        parts.append(check)
        print("  ".join(parts), flush=True)
        if not ok:
            self.failed.append(name)

    @contextlib.contextmanager
    def guard(self, name):
        """An exception inside fails check ``name``; the script goes on."""
        try:
            yield
        except Exception as e:
            traceback.print_exc()
            self.line(name, False, f"raised {type(e).__name__}: {e}")


def peak_mib() -> float:
    """Largest ``peak_bytes_in_use`` over the local devices, in MiB."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return max(peaks) / 2 ** 20


def compile_ahead(fn, *args, donate=()):
    """(compiled program, compile seconds)."""
    t0 = time.perf_counter()
    c = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
    return c, time.perf_counter() - t0


def run_timed(compiled, *args):
    t0 = time.perf_counter()
    out = jax.block_until_ready(compiled(*args))
    return out, time.perf_counter() - t0


@jax.jit
def _bits_equal(a, b):
    # one fused compare-and-reduce: an eager bitcast would copy each
    # [N, d] operand, several GiB at d ~ 1e8
    if jnp.issubdtype(a.dtype, jnp.floating):
        bits = jnp.dtype(f"uint{8 * a.dtype.itemsize}")
        a = jax.lax.bitcast_convert_type(a, bits)
        b = jax.lax.bitcast_convert_type(b, bits)
    return jnp.all(a == b)


def same_bits(a, b) -> bool:
    """Bit-for-bit equality (floats compared as their bit patterns)."""
    a, b = jnp.asarray(a), jnp.asarray(b)
    if a.shape != b.shape or a.dtype != b.dtype:
        return False
    return bool(_bits_equal(a, b))


def all_same_bits(xs, ys) -> bool:
    return all(same_bits(x, y) for x, y in zip(xs, ys, strict=True))


_finite = jax.jit(lambda x: jnp.all(jnp.isfinite(x)))


def all_finite(xs) -> bool:
    return all(bool(_finite(x)) for x in xs
               if jnp.issubdtype(x.dtype, jnp.floating))


def has_kernel(compiled) -> bool:
    """A compiled Pallas kernel shows as a Mosaic custom call."""
    return "tpu_custom_call" in compiled.as_text()


def round_fn(cfg):
    return lambda u, k: aggregate_round(u, cfg, k)[:3]


def random_stack(key, n: int, d: int, sharding=None):
    """Heavy-tailed client updates [n, d], made on the device."""
    return jax.jit(lambda k: jax.random.normal(k, (n, d)) ** 3,
                   out_shardings=sharding)(key)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_device(rep: Report, want_count: int | None):
    devs = jax.devices()
    dev = devs[0]
    print(f"# device platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devs)}", flush=True)
    count_ok = want_count is None or len(devs) == want_count
    rep.line("device", dev.platform == "tpu" and count_ok,
             f"platform={dev.platform} (want tpu) count={len(devs)}"
             + (f" (want {want_count})" if want_count else ""))
    if dev.platform != "tpu":
        raise SystemExit("chip_smoke: no TPU found; nothing else runs")


def phase_kernels(rep: Report, d: int = D_KERNEL, n_count: int = N_COUNT):
    """The six kernel entry points, each compiled and compared bit for bit
    with its jnp oracle in ``kernels/ref.py``."""
    lanes = ref.LANES
    rows = lambda x: x.reshape(-1, lanes)
    k = jax.random.split(jax.random.PRNGKey(0), 6)
    w = d // ref.GROUP
    mask = jax.random.bernoulli(k[0], 0.3, (d,)).astype(jnp.uint8)
    words = jax.random.bits(k[1], (w,), jnp.uint32)
    stack = jax.random.bits(k[2], (n_count, w), jnp.uint32)
    u = jax.random.normal(k[3], (d,)) ** 3
    uni = jax.random.uniform(k[4], (d,))
    sel = jax.random.bernoulli(k[5], 0.05, (d,)).astype(jnp.uint8)
    f, tau = jnp.float32(300.0), jnp.float32(1.0)
    cases = [
        ("pack_votes", ops.pack_votes, (mask,),
         lambda m: ref.pack_ref(rows(m)).reshape(-1)),
        ("unpack_votes", partial(ops.unpack_votes, d=d), (words,),
         lambda x: ref.unpack_ref(rows(x)).reshape(-1)[:d]),
        (f"count_votes(N={n_count})", partial(ops.count_votes, d=d),
         (stack,), lambda s: ref.popcount_accum_ref(
             s.reshape(n_count, -1, lanes)).reshape(-1)[:d]),
        ("quantize_flat", ops.quantize_flat, (u, uni, f),
         ref.stoch_quant_ref),
        ("pack_votes_threshold", ops.pack_votes_threshold, (u, tau),
         lambda s, t: ref.vote_pack_ref(rows(s), t).reshape(-1)),
        ("gather_quant_flat", ops.gather_quant_flat, (u, uni, sel, f),
         ref.gather_quant_ref),
    ]
    for name, fn, args, oracle in cases:
        with rep.guard(f"kernels/{name}"):
            c, cs = compile_ahead(fn, *args)
            out, rs = run_timed(c, *args)
            want = jax.jit(oracle)(*args)
            outs = out if isinstance(out, tuple) else (out,)
            wants = want if isinstance(want, tuple) else (want,)
            ident, kern = all_same_bits(outs, wants), has_kernel(c)
            rep.line(f"kernels/{name}", ident and kern,
                     f"d={d} bit-identical to ref={ident} "
                     f"tpu_custom_call={kern}", seconds=rs, compile_s=cs)


def phase_round(rep: Report, d: int = D_KERNEL, n: int = N_ROUND,
                chunk: int = STREAM_CHUNK):
    """One round per (mode, engine): monolithic and stream must agree bit
    for bit; the Pallas round must compile its kernel (topk) or equal the
    monolithic round (block mode keeps the jnp phase 2)."""
    key = jax.random.PRNGKey(0)
    u = random_stack(jax.random.PRNGKey(1), n, d)
    engines = (("monolithic", EngineSpec("monolithic")),
               ("stream", EngineSpec("stream", chunk=chunk)),
               ("pallas", EngineSpec("monolithic", use_pallas=True)))
    for vm, cm in MODES:
        outs = {}
        for label, eng in engines:
            name = f"round/{vm}-{cm}/{label}"
            with rep.guard(name):
                cfg = FediACConfig(vote_mode=vm, compact_mode=cm, engine=eng)
                c, cs = compile_ahead(round_fn(cfg), u, key)
                out, rs = run_timed(c, u, key)
                outs[label] = out
                fin = all_finite(out)
                check = f"N={n} d={d} finite={fin}"
                ok = fin
                if label == "stream":
                    ident = all_same_bits(out, outs["monolithic"])
                    check += f" bit-identical to monolithic={ident}"
                    ok &= ident
                elif label == "pallas" and cm == "block":
                    ident = all_same_bits(out, outs["monolithic"])
                    check += f" bit-identical to monolithic={ident}"
                    ok &= ident
                elif label == "pallas":
                    kern = has_kernel(c)
                    votes = same_bits(out[2], outs["monolithic"][2])
                    delta, res, _ = out
                    scale = float(jnp.max(jnp.abs(u)))
                    ef = bool(jnp.allclose(delta, (u - res).mean(axis=0),
                                           rtol=1e-5, atol=1e-5 * scale))
                    check += (f" tpu_custom_call={kern} counts equal "
                              f"monolithic={votes} error-feedback "
                              f"identity={ef}")
                    ok &= kern and votes and ef
                rep.line(name, ok, check, seconds=rs, compile_s=cs)
        del outs
    del u


def phase_big_round(rep: Report, d: int = D_BIG, n: int = N_ROUND):
    """One stream round at d ~ 1e8 (threshold/block), input donated."""
    key = jax.random.PRNGKey(0)
    u = random_stack(jax.random.PRNGKey(2), n, d)
    cfg = FediACConfig(vote_mode="threshold", compact_mode="block",
                       engine=EngineSpec("stream"))
    c, cs = compile_ahead(round_fn(cfg), u, key, donate=(0,))
    out, rs = run_timed(c, u, key)
    del u
    fin = all_finite(out)
    counts_ok = bool(jnp.all((out[2] >= 0) & (out[2] <= n)))
    rep.line("round/threshold-block/stream-1e8", fin and counts_ok,
             f"N={n} d={d} finite={fin} counts in [0,N]={counts_ok}",
             seconds=rs, compile_s=cs)


def phase_fl(rep: Report, task: dict = FL_TASK):
    """``run_federated`` over the packet transport, default engine and the
    Pallas engine: finite losses, final accuracy above chance."""
    spec = ScenarioSpec(name="chip-smoke", **task)
    clients, test = spec.make_task(0)
    chance = 1.0 / task["data_classes"]
    for label, engine in (("default", None),
                          ("pallas", EngineSpec("monolithic",
                                                use_pallas=True))):
        name = f"fl/packet/{label}"
        with rep.guard(name):
            fl = replace(spec.to_flconfig(0), engine=engine)
            t0 = time.perf_counter()
            hist = run_federated(list(clients), test, fl)
            secs = time.perf_counter() - t0
            fin = all(math.isfinite(x) for x in hist.loss)
            acc = hist.acc[-1]
            rep.line(name, fin and acc > chance,
                     f"rounds={len(hist)} clients={task['n_clients']} "
                     f"losses finite={fin} final acc={acc:.4f} "
                     f"(chance {chance:g})", seconds=secs)


def phase_sharded(rep: Report, d: int = D_BIG, n: int = N_ROUND):
    """The sharded engine over every chip, inputs placed on the coordinate
    axis, against the stream engine on the first chip: bit for bit."""
    from repro.core.shard_engine import shard_mesh
    devs = jax.devices()
    key = jax.random.PRNGKey(0)
    mesh = shard_mesh(len(devs))
    u = random_stack(jax.random.PRNGKey(2), n, d,
                     NamedSharding(mesh, P(None, "d")))
    u0 = jax.device_put(u, devs[0])
    mode = dict(vote_mode="threshold", compact_mode="block")
    cfg = FediACConfig(**mode, engine=EngineSpec("sharded"))
    c, cs = compile_ahead(round_fn(cfg), u, key)
    got, rs = run_timed(c, u, key)
    del u
    rep.line("four/sharded-1e8", all_finite(got),
             f"devices={len(devs)} N={n} d={d} finite={all_finite(got)}",
             seconds=rs, compile_s=cs)
    cfg = FediACConfig(**mode, engine=EngineSpec("stream"))
    c, cs = compile_ahead(round_fn(cfg), u0, key, donate=(0,))
    want, rs = run_timed(c, u0, key)
    del u0
    got = [jax.device_put(x, devs[0]) for x in got]
    ident = all_same_bits(got, want)
    rep.line("four/stream-1e8-one-chip", ident,
             f"sharded bit-identical to stream={ident}", seconds=rs,
             compile_s=cs)


def phase_train(rep: Report, argv=TRAIN_ARGV):
    """``python -m repro.launch.train`` in this process: finite losses,
    non-zero updates, parameters that moved."""
    from repro.launch import train as launch_train
    t0 = time.perf_counter()
    out = launch_train.train(launch_train.parse_args(argv))
    secs = time.perf_counter() - t0
    fin = all(math.isfinite(x) for x in out["losses"] + out["update_norms"])
    moved = (all(x > 0 for x in out["update_norms"])
             and out["param_sumsq"][0] != out["param_sumsq"][1])
    rep.line("four/train-qwen3-0.6b", fin and moved,
             f"{' '.join(argv)} losses={out['losses']} finite={fin} "
             f"params moved={moved}", seconds=secs)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip paths and their references")
    args = ap.parse_args(argv)
    enable_compile_cache()
    rep = Report()
    phase_device(rep, 4 if args.four_chips else None)
    phases = ([("four/sharded-1e8", phase_sharded),
               ("four/train-qwen3-0.6b", phase_train)] if args.four_chips
              else [("kernels", phase_kernels), ("round", phase_round),
                    ("round/threshold-block/stream-1e8", phase_big_round),
                    ("fl", phase_fl)])
    for name, phase in phases:
        with rep.guard(name):
            phase(rep)
    if rep.failed:
        print(f"chip_smoke: failed: {', '.join(rep.failed)}", file=sys.stderr)
        return 1
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
