"""The trainer path: one federated round is one call of the program's
distributed train step, ``repro.training.dist_step.make_train_step``
(the entry that ``python -m repro.launch.train`` uses), on a mesh of
clients.

The mesh is the traffic file's ``mesh`` (``data`` x ``model``); the
``data`` axis enumerates the clients, one to a chip, each holding the
whole registry model of the configuration. In a round each client runs
``local_steps`` SGD steps on its own ``seqs_per_client`` sequences, and
the clients aggregate their updates with FediAC across the chips (the
vote ``psum``, the scale's ``pmax`` and phase 2's integer ``psum``, in
``repro.core.fediac.fediac_allreduce``), and everyone applies the mean.

The weights are made from the seed by the benchmark's own
:func:`bench.reference.mamba2_lm.init`, in the registry's layout, and
handed to the program; each round's tokens are uniform over the
vocabulary, made on the device from the seed and the round, the target
being the next token. Set-up compiles the step and drives it through
the first ``CHECKED`` rounds, keeping the parameters after the first
and after the last of them; the window goes on from that same state.

Correctness: after the window the plain reference (the model of
``mamba2_lm`` at float32 ``highest`` precision, the aggregation of
``fediac_allreduce``) runs the same first rounds from the same weights,
tokens and keys. Compared, each by the limit in :data:`LIMITS`: the
rounds' mean client losses, the first round's parameter change, and the
change after ``CHECKED`` rounds, both by the worst leaf's norm.
"""

from __future__ import annotations

import contextlib
import statistics
import sys
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from bench import core
from bench.reference import fediac_allreduce as fa
from bench.reference import fediac_round as fr
from bench.reference import mamba2_lm as lm

CHECKED = 3          # rounds the reference follows
# the numbers compared, and their limits. A limit is set from on-chip
# readings of the program (the lower) and of the control and the faults
# (the upper); this cell has had none yet (PERF.md, Open questions 2),
# so no limit is set and the check refuses to decide.
LIMITS = {"loss_gap": None, "update_gap": None, "change_gap": None}
# what runs in the program's place: the program; the reference computed
# in bfloat16 (the control); and faults planted in the program or in the
# reference put in its place
VARIANTS = ("program", "control", "state_unchanged", "half_batch", "no_exchange",
            "residual_bf16", "no_D", "three_clients")


def fediac_config(traffic: dict):
    from repro.api import FediACConfig
    return FediACConfig(
        k_frac=traffic["k_frac"], a=traffic["a"], bits=traffic["bits"],
        capacity_frac=traffic["capacity_frac"], vote_mode=traffic["vote_mode"],
        compact_mode=traffic["compact_mode"], alpha=traffic["alpha"],
        block_size=traffic["block_size"], granularity=traffic["granularity"])


def arch(conf: dict, traffic: dict, residual_dtype: str = "float32"):
    """The registry model at the configuration file's sizes, with the
    traffic mix's local steps, microbatches and FediAC."""
    from repro.configs import registry
    return registry.get(conf["registry"]).with_(
        n_layers=conf["n_layers"], d_model=conf["d_model"], vocab=conf["vocab"],
        ssm_heads=conf["ssm_heads"], ssm_head_dim=conf["ssm_head_dim"],
        ssm_groups=conf["ssm_groups"], ssm_state=conf["ssm_state"],
        ssm_chunk=conf["ssm_chunk"], conv_width=conf["conv_width"],
        microbatch=traffic["microbatch"], fl_local_steps=traffic["local_steps"],
        fediac=fediac_config(traffic), residual_dtype=residual_dtype)


def make_batch(key, t, rows: int, seq: int, vocab: int):
    x = jax.random.randint(jax.random.fold_in(key, t), (rows, seq + 1), 0, vocab,
                           jnp.int32)
    return {"tokens": x[:, :-1], "targets": x[:, 1:]}


@jax.jit
def _round_key(key, t):
    return jax.random.fold_in(key, t)


def _host(tree):
    return [np.asarray(x) for x in jax.tree.leaves(tree)]


@contextlib.contextmanager
def _local_exchange():
    """Fault ``no_exchange``: while the step is traced, each client
    aggregates alone (no vote ``psum``, ``pmax`` or integer ``psum``) and
    applies its own compressed update."""
    from repro.training import dist_step
    agg = dist_step._aggregate_flat
    dist_step._aggregate_flat = lambda cfg, u, r, key, axes: agg(cfg, u, r, key, ())
    try:
        yield
    finally:
        dist_step._aggregate_flat = agg


class Program:
    """The timed path: the program's train step, jitted with the
    parameters and the residual donated."""

    def __init__(self, cfg, mesh, traffic, variant="program"):
        from repro.models.model import init_params
        from repro.training.dist_step import make_train_step
        bundle = make_train_step(cfg, mesh, lr=traffic["lr"])
        shard = lambda spec: jax.tree.map(lambda s: NamedSharding(mesh, s), spec)
        self.param_sharding = shard(bundle.params_spec)
        self.residual_sharding = shard(bundle.residual_spec)
        self.shapes = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.key(0))
        self.n, self.rdt = bundle.n_clients, jnp.dtype(cfg.residual_dtype)
        step = bundle.step
        if variant == "state_unchanged":
            def step(params, residual, batch, key):
                return params, residual, bundle.step(params, residual, batch, key)[2]
        elif variant == "half_batch":
            # each client's first half of its sequences, the mean over those
            def step(params, residual, batch, key):
                half = {k: v.reshape(self.n, -1, v.shape[-1])[:, :v.shape[0] // self.n // 2]
                        .reshape(-1, v.shape[-1]) for k, v in batch.items()}
                return bundle.step(params, residual, half, key)
        self.step = jax.jit(step, donate_argnums=(0, 1))
        self.planted = _local_exchange if variant == "no_exchange" else contextlib.nullcontext

    def init_residual(self, params):
        got = jax.tree.map(lambda x: (x.shape, x.dtype), params)
        want = jax.tree.map(lambda x: (x.shape, x.dtype), self.shapes)
        if got != want:
            raise ValueError(f"weights do not fit the registry model: {got} vs {want}")
        return jax.jit(lambda p: jax.tree.map(
            lambda x: jnp.zeros((self.n, *x.shape), self.rdt), p),
            out_shardings=self.residual_sharding)(params)


class Reference:
    """The plain round: each client's local steps (``mamba2_lm``, one
    client to a chip), the plain aggregation (``fediac_allreduce``, on the
    first chip, over the stacked uploads), and the mean applied."""

    def __init__(self, sz, traffic, mesh, dtype="float32", drop_d=False,
                 summed=None):
        self.n = mesh.shape["data"]
        self.p, self.summed = fr.params(traffic), summed
        self.dev0 = mesh.devices.flat[0]
        self.param_sharding = NamedSharding(mesh, P())
        clients = NamedSharding(mesh, P("data"))
        seq = traffic["seq_len"]
        one = partial(lm.local_update, lr=traffic["lr"], sz=sz,
                      steps=traffic["local_steps"], micro=traffic["microbatch"],
                      dtype=dtype, drop_d=drop_d)

        def clients_fn(params, batch):
            per = lambda v: jax.lax.with_sharding_constraint(
                v.reshape(self.n, -1, seq), clients)
            u, loss = jax.vmap(lambda t, y: one(params, t, y))(
                per(batch["tokens"]), per(batch["targets"]))
            norms = [jnp.sqrt(jnp.sum(jnp.square(jnp.mean(x, axis=0))))
                     for x in jax.tree.leaves(u)]
            flat = jnp.concatenate([x.reshape(self.n, -1) for x in jax.tree.leaves(u)], 1)
            return flat, jnp.mean(loss), jnp.stack(norms)

        self.clients = jax.jit(clients_fn)
        self.apply = jax.jit(
            lambda params, mean: jax.tree.unflatten(
                jax.tree.structure(params),
                [w - m.reshape(w.shape) for w, m in zip(
                    jax.tree.leaves(params),
                    jnp.split(mean, np.cumsum([x.size for x in jax.tree.leaves(params)])[:-1]))]),
            out_shardings=self.param_sharding)
        self.planted = contextlib.nullcontext

    def init_residual(self, params):
        d = sum(x.size for x in jax.tree.leaves(params))
        return jax.device_put(jnp.zeros((self.n, d), jnp.float32), self.dev0)

    def step(self, params, residual, batch, key):
        flat, loss, norms = self.clients(params, batch)
        mean, residual = fa.allreduce(jax.device_put(flat, self.dev0), residual,
                                      key, self.p, self.summed)
        del flat
        mean = jax.device_put(mean, self.param_sharding)
        return self.apply(params, mean), residual, {"loss": loss, "update_norms": norms}


def chain(variant, conf, traffic, mesh):
    sz = lm.sizes(conf)
    if variant == "control":
        return Reference(sz, traffic, mesh, dtype="bfloat16")
    if variant == "no_D":
        return Reference(sz, traffic, mesh, drop_d=True)
    if variant == "three_clients":
        return Reference(sz, traffic, mesh, summed=mesh.shape["data"] - 1)
    rdt = "bfloat16" if variant == "residual_bf16" else "float32"
    return Program(arch(conf, traffic, rdt), mesh, traffic, variant)


def leaf_gap(p0, prog, ref, skip):
    """Worst leaf's gap between the norms of the program's and the
    reference's change from ``p0``, over the larger of that leaf's
    reference norm and the median leaf's; ``skip`` leaves left out. The
    median is of the leaves that the reference moves: FediAC keeps no
    coordinate of some leaves in a round, and those have no norm to set
    a scale."""
    norm = lambda a, b: float(np.linalg.norm((a.astype(np.float64) - b).ravel()))
    dp = [norm(b, a) for a, b in zip(p0, prog)]
    dr = [norm(b, a) for a, b in zip(p0, ref)]
    moved = [y for y in dr if y > 0]
    med = statistics.median(moved) if moved else 0.0
    gaps = [abs(x - y) / max(y, med) if max(y, med) > 0 else
            (0.0 if x == y else float("inf"))
            for i, (x, y) in enumerate(zip(dp, dr)) if i not in skip]
    return max(gaps), dp, dr


class Run:
    """Set-up on construction (weights, compile, the first ``CHECKED``
    rounds); then :meth:`round` per timed round and :meth:`check` after
    the window."""

    def __init__(self, cell: dict, seed: int, devs, variant: str = "program"):
        from repro.compat import make_mesh
        self.conf, self.traffic = cell["config_data"], cell["traffic_data"]
        tr = self.traffic
        t0 = time.perf_counter()
        self.mesh = make_mesh(tuple(tr["mesh"]), ("data", "model"), devices=devs)
        self.chain = chain(variant, self.conf, tr, self.mesh)
        self.kw, self.kdata, self.kagg = jax.random.split(core.seed_key(seed), 3)
        self.init = jax.jit(partial(lm.init, sz=lm.sizes(self.conf)),
                            out_shardings=self.chain.param_sharding)
        self.params = self.init(self.kw)
        self.residual = self.chain.init_residual(self.params)
        rows = self.mesh.shape["data"] * tr["seqs_per_client"]
        self.inputs = jax.jit(
            partial(make_batch, rows=rows, seq=tr["seq_len"], vocab=self.conf["vocab"]),
            out_shardings=NamedSharding(self.mesh, P("data", None)))
        jax.block_until_ready((self.params, self.residual))
        t1 = time.perf_counter()
        self.t, self.losses, self.kept = 0, [], {}
        with self.chain.planted():
            self.round()          # compiles every program the window runs
        t2 = time.perf_counter()
        self.kept[1] = _host(self.params)
        while self.t < CHECKED:
            self.round()
        self.kept[CHECKED] = _host(self.params)
        self.setup_phases = {"data_s": t1 - t0, "compile_round_s": t2 - t1,
                             "rounds_s": time.perf_counter() - t2}

    def round(self):
        with jax.profiler.TraceAnnotation("input"):
            batch = self.inputs(self.kdata, self.t)
            key = _round_key(self.kagg, self.t)
        with jax.profiler.TraceAnnotation("round"):
            self.params, self.residual, metrics = self.chain.step(
                self.params, self.residual, batch, key)
        with jax.profiler.TraceAnnotation("block"):
            jax.block_until_ready((self.params, self.residual, metrics))
        loss = float(metrics["loss"])
        self.losses.append(loss)
        print(f"# round {self.t} loss={loss!r}", file=sys.stderr, flush=True)
        self.t += 1

    def work(self) -> dict:
        return core.load_module("work", "train").required(self.conf, self.traffic)

    def check(self) -> list:
        """``[(name, value, limit), ...]`` of :meth:`readings`."""
        unset = sorted(n for n, lim in LIMITS.items() if lim is None)
        if unset:
            raise RuntimeError(f"no limit set for {unset}: limits come from "
                               "on-chip readings (PERF.md, Open questions 2)")
        return [(n, v, LIMITS[n]) for n, v in self.readings()]

    def readings(self) -> list:
        """``[(name, value), ...]``: the reference runs the first
        ``CHECKED`` rounds after the program's state is freed."""
        t0 = time.perf_counter()
        del self.params, self.residual, self.chain
        ref = Reference(lm.sizes(self.conf), self.traffic, self.mesh)
        params = self.init(self.kw)
        p0 = _host(params)
        residual = ref.init_residual(params)
        losses, kept = [], {}
        for t in range(CHECKED):
            params, residual, m = ref.step(params, residual,
                                           self.inputs(self.kdata, t),
                                           _round_key(self.kagg, t))
            losses.append(float(m["loss"]))
            if t == 0:
                norms = np.asarray(m["update_norms"])
            if t + 1 in self.kept:
                kept[t + 1] = _host(params)
        del params, residual
        t_ref = time.perf_counter() - t0
        # leaves whose reference update is nought to rounding move by
        # round-off alone: under a thousandth of the median leaf's
        skip = {i for i, x in enumerate(norms) if x < 1e-3 * np.median(norms)}
        loss_gap = max(abs(a - b) / abs(b) for a, b in zip(self.losses, losses))
        update_gap, dp, dr = leaf_gap(p0, self.kept[1], kept[1], skip)
        change_gap, cp, cr = leaf_gap(p0, self.kept[CHECKED], kept[CHECKED], skip)
        diff = lambda a, b: np.sqrt(sum(float(np.sum((x.astype(np.float64) - y) ** 2))
                                        for x, y in zip(a, b)))
        print(f"# losses program={self.losses[:CHECKED]!r} reference={losses!r}\n"
              f"# update_norms program={dp!r} reference={dr!r}\n"
              f"# change_norms program={cp!r} reference={cr!r}\n"
              f"# update_rel_l2={diff(self.kept[1], kept[1]) / diff(p0, kept[1])!r} "
              f"skip={sorted(skip)!r}", file=sys.stderr, flush=True)
        self.check_s = (t_ref, time.perf_counter() - t0)
        return [("loss_gap", loss_gap), ("update_gap", update_gap),
                ("change_gap", change_gap)]
