"""The round path: one federated round is one call of
``repro.api.aggregate_round`` on the ``[N, d]`` stack of client uploads.

Each round's input is the set-up's local-update stack ``g`` plus the
residual that the previous round returned (the error feedback an FL loop
carries), added by the harness. The add takes the residual's buffer and
the round takes the input's, so the device holds ``g`` and one more
stack. The round ends when its outputs are ready: a deployment's round
closes when the switch returns the aggregate.

``g`` is made on the device from the seed: ``g_i = s + z_i`` with ``s``
shared by all clients and ``z_i`` each client's own, both the cube of a
standard normal. Real clients' updates agree on a common direction, and
that agreement is what the vote measures.

Correctness: the rounds form one chain from the seed. After the window
the plain reference (``bench/reference/fediac_round.py``) replays the
same chain, and the last round's vote counts, delta and residual stack
must equal the program's bit for bit.
"""

from __future__ import annotations

import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import core
from bench.reference import fediac_round as ref


def make_updates(key, n: int, d: int):
    ks, kz = jax.random.split(key)
    s = jax.random.normal(ks, (d,), jnp.float32) ** 3
    z = jax.vmap(lambda k: jax.random.normal(k, (d,), jnp.float32))(
        jax.random.split(kz, n)) ** 3
    return s[None, :] + z


@partial(jax.jit, donate_argnums=1)
def _add(g, r):
    return g + r


@jax.jit
def _round_key(key, t):
    return jax.random.fold_in(key, t)


def fediac_config(traffic: dict):
    from repro.api import EngineSpec, FediACConfig
    return FediACConfig(
        k_frac=traffic["k_frac"], a=traffic["a"], bits=traffic["bits"],
        capacity_frac=traffic["capacity_frac"],
        vote_mode=traffic["vote_mode"], compact_mode=traffic["compact_mode"],
        alpha=traffic.get("alpha", -1.0),
        block_size=traffic.get("block_size", 4096),
        engine=EngineSpec(traffic["engine"], chunk=traffic.get("chunk", 0)))


def program_round(traffic: dict):
    """The timed entry: ``(u, key) -> (delta, residual, counts)``, ``u``
    donated."""
    from repro.api import aggregate_round
    cfg = fediac_config(traffic)
    return jax.jit(lambda u, key: aggregate_round(u, cfg, key)[:3],
                   donate_argnums=0)


class Run:
    """Set-up on construction (data, compile, one warm round); then
    :meth:`round` per timed round and :meth:`check` after the window."""

    def __init__(self, cell: dict, seed: int, devs, round_fn=None):
        conf, self.traffic = cell["config_data"], cell["traffic_data"]
        self.n, self.d = int(self.traffic["clients"]), int(conf["d"])
        t0 = time.perf_counter()
        kd, self.key = jax.random.split(core.seed_key(seed))
        self.g = jax.jit(make_updates, static_argnums=(1, 2))(kd, self.n, self.d)
        self.inputs = partial(_add, self.g)
        self.r = jnp.zeros((self.n, self.d), jnp.float32)
        jax.block_until_ready((self.g, self.r))
        t1 = time.perf_counter()
        self.step = round_fn or program_round(self.traffic)
        self.t = 0
        self.out = None
        self.round()  # warm: every program the window runs, compiled
        self.setup_phases = {"data_s": t1 - t0, "warm_s": time.perf_counter() - t1}

    def round(self):
        with jax.profiler.TraceAnnotation("input"):
            u = self.inputs(self.r)
            k = _round_key(self.key, self.t)
        with jax.profiler.TraceAnnotation("round"):
            delta, self.r, counts = self.step(u, k)
        with jax.profiler.TraceAnnotation("block"):
            jax.block_until_ready((delta, self.r, counts))
        self.out = (delta, counts)
        self.t += 1

    def work(self) -> dict:
        return core.load_module("work", "round").required(self.n, self.d)

    def check(self) -> list:
        """``[(name, value, limit), ...]`` for the last round, against the
        reference replaying the whole chain. Frees the program's state."""
        t0 = time.perf_counter()
        delta_p = np.asarray(self.out[0])
        counts_p = np.asarray(self.out[1])
        resid_p = np.asarray(self.r)
        del self.r, self.out, self.step
        p = ref.params(self.traffic)
        r = jnp.zeros((self.n, self.d), jnp.float32)
        for t in range(self.t):
            delta, r, counts = ref.round_step(self.inputs(r), _round_key(self.key, t), p)
        jax.block_until_ready(r)
        t_ref = time.perf_counter() - t0
        bits = lambda x: np.asarray(x).view(np.uint32)
        votes_off = int(np.count_nonzero(counts_p != np.asarray(counts)))
        delta_off = int(np.count_nonzero(bits(delta_p) != bits(delta)))
        resid_off = sum(int(np.count_nonzero(bits(resid_p[i]) != bits(r[i])))
                        for i in range(self.n))
        self.check_s = (t_ref, time.perf_counter() - t0)
        return [("votes_off", votes_off, 0), ("delta_off", delta_off, 0),
                ("residual_off", resid_off, 0)]
