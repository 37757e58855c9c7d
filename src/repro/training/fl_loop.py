"""The federated-learning simulator: N clients, E local steps, a pluggable
in-network aggregator, and the M/G/1 switch wall-clock model.

This is the engine behind every paper-reproduction benchmark (Fig. 2-4,
Tables I-II).  The task model is a small MLP classifier over the synthetic
non-IID classification data (DESIGN.md §6 — the box is offline, no
CIFAR/FEMNIST); learning dynamics, compression behaviour and the queuing
model are the paper's.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field, replace

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import load_run_state, save_run_state
from repro.core import engines
from repro.core.baselines import make_transport
from repro.core.fediac import FediACConfig
from repro.validate import (check_at_least, check_choice,
                            check_finite_at_least, check_positive_finite)
from repro.obs.probe import as_probe
from repro.obs.scopes import scope
from repro.switch import SwitchProfile, client_rates, n_packets, round_wall_clock


# ---------------------------------------------------------------------------
# task model: MLP classifier
# ---------------------------------------------------------------------------

def init_mlp(key, dims: tuple[int, ...]):
    ks = jax.random.split(key, len(dims) - 1)
    return [{"w": jax.random.normal(k, (a, b)) * (2.0 / a) ** 0.5,
             "b": jnp.zeros((b,))}
            for k, a, b in zip(ks, dims[:-1], dims[1:])]


def mlp_apply(params, x):
    for i, lyr in enumerate(params):
        x = x @ lyr["w"] + lyr["b"]
        if i < len(params) - 1:
            x = jax.nn.relu(x)
    return x


def _ce_loss(params, x, y):
    logits = mlp_apply(params, x)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
    return (logz - gold).mean()


def accuracy(params, x, y) -> float:
    pred = jnp.argmax(mlp_apply(params, x), axis=-1)
    return float((pred == y).mean())


# ---------------------------------------------------------------------------
# the FL loop
# ---------------------------------------------------------------------------

@dataclass
class FLConfig:
    """One federated-learning experiment.

    ``transport`` selects how each round's bytes reach the aggregate
    (DESIGN.md §9): ``"memory"`` calls the aggregator directly and prices
    the round with the analytic M/G/1 ``round_wall_clock`` model (the
    seed behavior); ``"packet"`` pushes the round through the executable
    packet dataplane (``repro.netsim``) — Poisson packet streams, loss +
    retransmission, stragglers, partial participation, register windows
    and the leaf->root switch hierarchy, all configured by ``net`` (a
    ``netsim.NetConfig``) — and uses the *simulated* wall-clock instead.
    The FediAC packet round runs as one jitted fixed-shape core
    (DESIGN.md §13); the sweep fleet vmaps the same core, so packet
    scenarios executed here and through ``repro.sweep`` are bit-identical.
    With ``net`` at its lossless full-participation defaults the packet
    transport is bit-identical to the in-memory FediAC engine.  ``net``
    may also be a ``netsim.FaultConfig`` (DESIGN.md §14): the chaos
    dataplane — bursty loss, crashes, duplicates, register faults —
    bit-identical to the plain core at zero fault rates.  Or a
    ``netsim.AsyncConfig`` (DESIGN.md §17): the async quorum-or-deadline
    close — the switch folds phase-2 payloads as they land and closes
    the round once ``quorum_frac`` of the uploaders arrive or the
    ``round_deadline_s`` budget expires, folding late updates into the
    next round at a staleness-decayed weight (or bouncing them to the
    client's residual) instead of waiting for stragglers.  The pending
    carry buffer rides the aggregator-state slot, so ``ckpt_path``
    checkpoints it round-granularly and kill-and-resume reproduces the
    async history bit-exactly; at full quorum with no deadline the async
    transport is bit-identical to the synchronous packet core.

    Crash-safe recovery (DESIGN.md §14): set ``ckpt_path`` to persist the
    loop's inter-round state (model, error-feedback stack, PRNG key,
    aggregator state, pricing accumulators, history) atomically every
    ``ckpt_every`` rounds; ``resume=True`` restores it and continues.  A
    run killed at round k and resumed reproduces the uninterrupted
    ``FLHistory`` bit-exactly — the save round-trips every carried value
    at full precision and all per-round randomness is (seed, round)-keyed.
    """

    n_clients: int = 20
    rounds: int = 60
    local_steps: int = 5           # E
    batch: int = 32
    lr0: float = 0.1
    lr_tau: float = 20.0           # lr_t = lr0 / (1 + sqrt(t)/tau)   (paper V-A1)
    aggregator: str = "fediac"
    agg_kwargs: dict = field(default_factory=dict)
    use_pallas: bool | None = None  # DEPRECATED: use engine=EngineSpec(
                                    # use_pallas=True).  Still forwards into
                                    # FediACConfig.use_pallas, warning once.
    engine: object | None = None    # override FediACConfig.engine: a
                                    # registered name ("monolithic" |
                                    # "stream" | "sharded") or a
                                    # core.engines.EngineSpec; every engine
                                    # is bit-identical (DESIGN.md §12, §16)
    switch: SwitchProfile = field(default_factory=SwitchProfile.high)
    local_train_s: float = 0.1     # paper: 0.1 (FEMNIST) .. 3 (CIFAR-100)
    transport: str = "memory"      # "memory" | "packet"  (DESIGN.md §9)
    net: object | None = None      # netsim.NetConfig (or FaultConfig, §14)
                                   # for transport="packet"
    seed: int = 0
    # crash-safe recovery (DESIGN.md §14)
    ckpt_path: str | None = None   # round-granular run-state checkpoint file
    ckpt_every: int = 1            # save every k completed rounds
    resume: bool = False           # restore ckpt_path (if present) and
                                   # continue — bit-exact vs uninterrupted

    def __post_init__(self):
        check_at_least("n_clients", self.n_clients, 1)
        check_at_least("rounds", self.rounds, 0)
        check_at_least("local_steps", self.local_steps, 1)
        check_at_least("batch", self.batch, 1)
        check_positive_finite("lr0", self.lr0)
        check_positive_finite("lr_tau", self.lr_tau)
        check_finite_at_least("local_train_s", self.local_train_s, 0.0)
        check_choice("transport", self.transport, ("memory", "packet"))
        check_at_least("ckpt_every", self.ckpt_every, 1)
        if self.engine is not None:
            engines.get(self.engine)   # registered name or EngineSpec


@dataclass
class RoundRecord:
    """One completed round's observations.

    ``wall_clock`` and ``traffic_mb`` are *cumulative* (seconds / MB since
    round 1), matching what the legacy parallel lists always stored.
    """

    acc: float
    wall_clock: float      # cumulative seconds
    traffic_mb: float      # cumulative MB (upload + download, all clients)
    loss: float

    def to_metrics(self) -> dict:
        return {"acc": self.acc, "wall_clock_cum_s": self.wall_clock,
                "traffic_cum_mb": self.traffic_mb, "loss": self.loss}


class FLHistory:
    """Per-round :class:`RoundRecord` list behind the legacy list-of-floats
    attribute API.

    The legacy attributes (``acc``/``wall_clock``/``traffic_mb``/``loss``)
    are read-only *views* — fresh float lists computed from ``records`` —
    so ``sweep/runner.py`` and ``checkpoint/ckpt.py`` round-trip
    bit-exactly through them, but appending to a view is lost: grow a
    history with :meth:`append_round`.
    """

    __slots__ = ("records",)

    def __init__(self, acc=(), wall_clock=(), traffic_mb=(), loss=()):
        self.records = [RoundRecord(a, w, m, l)
                        for a, w, m, l in zip(acc, wall_clock,
                                              traffic_mb, loss)]

    def append_round(self, *, acc: float, wall_clock: float,
                     traffic_mb: float, loss: float) -> RoundRecord:
        rec = RoundRecord(acc, wall_clock, traffic_mb, loss)
        self.records.append(rec)
        return rec

    # legacy parallel-list API (read-only views over ``records``)
    @property
    def acc(self) -> list:
        return [r.acc for r in self.records]

    @property
    def wall_clock(self) -> list:
        return [r.wall_clock for r in self.records]

    @property
    def traffic_mb(self) -> list:
        return [r.traffic_mb for r in self.records]

    @property
    def loss(self) -> list:
        return [r.loss for r in self.records]

    def __len__(self) -> int:
        return len(self.records)

    def __eq__(self, other) -> bool:
        return (isinstance(other, FLHistory)
                and self.records == other.records)

    def __repr__(self) -> str:
        return f"FLHistory({len(self.records)} rounds)"

    def acc_at_time(self, t: float) -> float:
        """Final accuracy achieved within a wall-clock budget (Fig. 2 readout)."""
        best = 0.0
        for r in self.records:
            if r.wall_clock <= t:
                best = max(best, r.acc)
        return best

    def traffic_to_accuracy(self, target: float) -> float | None:
        """MB consumed until the target test accuracy (Tables I/II readout)."""
        for r in self.records:
            if r.acc >= target:
                return r.traffic_mb
        return None


def _stack_clients(clients, batch: int, rng: np.random.Generator):
    """Pad client datasets to a common size (resampling) for vmap."""
    size = max(max(len(c.y) for c in clients), batch)
    xs, ys = [], []
    for c in clients:
        idx = np.arange(len(c.y))
        if len(idx) < size:
            idx = np.concatenate([idx, rng.choice(len(c.y), size - len(idx))])
        xs.append(c.x[idx])
        ys.append(c.y[idx])
    return jnp.asarray(np.stack(xs)), jnp.asarray(np.stack(ys))


def make_client_round(unravel, batch: int, local_steps: int):
    """The per-round local-training program in explicit, vmappable form.

    Everything a round depends on — the stacked client data ``(cx, cy)``,
    the per-client sampling bound ``size`` and the learning rate — enters
    through arguments rather than closures, so the same function serves
    both the sequential loop (data baked in as constants under ``jit``)
    and the fleet runner (data batched along a leading scenario axis under
    ``vmap``).  ``size`` may be a traced scalar: ``jax.random.randint``
    draws the same values for a traced bound as for the static one, which
    is what keeps fleet cells bit-identical to their sequential runs even
    when cells are padded to a common dataset size.
    """
    grad_fn = jax.grad(_ce_loss)

    def client_round(flat_params, key, lr, cx, cy, size):
        """E local SGD steps on every client (vmapped). Returns U stack."""
        def per_client(cxi, cyi, k):
            w = unravel(flat_params)

            def step(w, k):
                idx = jax.random.randint(k, (batch,), 0, size)
                g = grad_fn(w, cxi[idx], cyi[idx])
                w = jax.tree_util.tree_map(lambda p, gg: p - lr * gg, w, g)
                return w, _ce_loss(w, cxi[idx], cyi[idx])

            ks = jax.random.split(k, local_steps)
            w, losses = jax.lax.scan(step, w, ks)
            u = flat_params - jax.flatten_util.ravel_pytree(w)[0]
            return u, losses.mean()

        ks = jax.random.split(key, cx.shape[0])
        return jax.vmap(per_client)(cx, cy, ks)

    return client_round


# Folding the error-feedback carry into the fresh update stack is the round's
# first [N, d] op; donating the stack lets XLA write the sum in place instead
# of allocating another [N, d] buffer (values are the same add either way).
_carry_in = jax.jit(lambda u_stack, e_stack: u_stack + e_stack,
                    donate_argnums=(0,))


def run_federated(clients, test, flcfg: FLConfig, *, hidden=(128, 64),
                  probe=None) -> FLHistory:
    """Run the FL loop; ``probe`` is an optional ``repro.obs`` RoundProbe.

    Probes observe only host-side values the loop already computes (plus
    the transport's stats dict), so any probe — including the default
    ``NullProbe`` — leaves every compiled program and every output
    bit-identical (DESIGN.md §15).
    """
    probe = as_probe(probe)
    rng = np.random.default_rng(flcfg.seed)
    dim = clients[0].x.shape[1]
    n_classes = clients[0].n_classes
    key = jax.random.PRNGKey(flcfg.seed)
    params = init_mlp(key, (dim, *hidden, n_classes))
    flat0, unravel = jax.flatten_util.ravel_pytree(params)
    d = flat0.size

    cx, cy = _stack_clients(clients, flcfg.batch, rng)
    n, size = cy.shape
    assert n == flcfg.n_clients, (n, flcfg.n_clients)

    agg_kwargs = dict(flcfg.agg_kwargs)
    if flcfg.aggregator == "fediac":
        overrides = {}
        if flcfg.use_pallas is not None:
            engines._warn_once(
                "FLConfig.use_pallas",
                "pass FLConfig(engine=EngineSpec(name=..., use_pallas=True))")
            overrides["use_pallas"] = flcfg.use_pallas
        if flcfg.engine is not None:
            overrides["engine"] = engines.get(flcfg.engine)
        if overrides:
            base_cfg = agg_kwargs.get("cfg", FediACConfig())
            agg_kwargs["cfg"] = replace(base_cfg, **overrides)
    rates = client_rates(n, flcfg.seed)
    transport = make_transport(flcfg.aggregator, transport=flcfg.transport,
                               net=flcfg.net, profile=flcfg.switch,
                               rates=rates, local_train_s=flcfg.local_train_s,
                               **agg_kwargs)

    client_round = make_client_round(unravel, flcfg.batch, flcfg.local_steps)
    def local_train(flat_params, key, lr):
        with scope("local_train"):
            return client_round(flat_params, key, lr, cx, cy, size)

    local_round = jax.jit(local_train)
    # host-side observation only: wrap_jit counts compiles/cache hits
    # around the same jitted callables (NullProbe returns them unchanged),
    # and transports with probe support report their stats dicts.
    local_round = probe.wrap_jit(local_round, "local_round")
    carry_in = probe.wrap_jit(_carry_in, "carry_in")
    attach = getattr(transport, "attach_probe", None)
    if attach is not None:
        attach(probe)

    e_stack = jnp.zeros((n, d))
    flat = flat0
    agg_state = None
    hist = FLHistory([], [], [], [])
    t_cum = 0.0
    mb_cum = 0.0
    start_round = 0
    if flcfg.resume and flcfg.ckpt_path and os.path.exists(flcfg.ckpt_path):
        # restore the inter-round state saved after the last completed
        # round; everything re-derived above (data, rates, transport,
        # jitted programs) is a pure function of the config, so the
        # restored state is sufficient for bit-exact continuation.
        st = load_run_state(flcfg.ckpt_path)
        flat = jnp.asarray(st["flat"])
        e_stack = jnp.asarray(st["e_stack"])
        key = jnp.asarray(st["key"])
        agg_state = st["agg_state"]
        start_round = int(st["round"])
        t_cum, mb_cum = st["t_cum"], st["mb_cum"]
        hist = FLHistory(**st["history"])
    xt, yt = jnp.asarray(test.x), jnp.asarray(test.y)

    if probe.enabled:
        probe.run_start(kind="fl_run", aggregator=flcfg.aggregator,
                        transport=flcfg.transport,
                        engine=(engines.get(flcfg.engine).name
                                if flcfg.engine is not None else None),
                        n_clients=n, rounds=flcfg.rounds, seed=flcfg.seed,
                        resumed_from=start_round if start_round else None)

    for t in range(start_round + 1, flcfg.rounds + 1):
        with probe.span("round", round=t):
            lr = flcfg.lr0 / (1.0 + np.sqrt(t) / flcfg.lr_tau)
            key, k1, k2 = jax.random.split(key, 3)
            with probe.span("local-train", round=t):
                u_stack, losses = local_round(flat, k1, lr)
                u_stack = carry_in(u_stack, e_stack)
            with probe.span("aggregate", round=t):
                res = transport.round(u_stack, agg_state, k2, t)
            delta, e_stack, agg_state = res.delta, res.residuals, res.state
            traffic, load = res.traffic, res.load
            flat = flat - delta

            sim_t0 = t_cum
            if res.wall_clock_s is not None:
                t_cum += res.wall_clock_s       # packet-simulated round time
            else:
                down_packets = n_packets(traffic.total_bytes)
                t_cum += round_wall_clock(
                    packets_per_client=load.packets_per_client,
                    download_packets=down_packets, rates=rates,
                    profile=flcfg.switch,
                    local_train_s=flcfg.local_train_s, aligned=load.aligned)
            # uploads come from the clients that actually sent this round
            # (the packet transport reports exact bytes — dropped voters
            # still spent phase 1); the broadcast reaches all N clients.
            up_bytes = (res.upload_bytes if res.upload_bytes is not None
                        else traffic.total_bytes * res.n_active)
            upload_mb = up_bytes / 1e6
            download_mb = traffic.total_bytes * n / 1e6
            mb_cum += upload_mb + download_mb
            with probe.span("eval", round=t):
                acc_t = accuracy(unravel(flat), xt, yt)
            rec = hist.append_round(acc=acc_t, wall_clock=t_cum,
                                    traffic_mb=mb_cum,
                                    loss=float(losses.mean()))
            if probe.enabled:
                _emit_round(probe, t, rec, res, sim_t0, t_cum,
                            up_bytes, traffic.total_bytes * n)
            if (flcfg.ckpt_path and flcfg.ckpt_every > 0
                    and (t % flcfg.ckpt_every == 0 or t == flcfg.rounds)):
                with probe.span("ckpt", round=t):
                    save_run_state(flcfg.ckpt_path, flat=flat,
                                   e_stack=e_stack, key=key,
                                   agg_state=agg_state, round_idx=t,
                                   t_cum=t_cum, mb_cum=mb_cum, history=hist)
    return hist


def _emit_round(probe, t: int, rec: RoundRecord, res, sim_t0: float,
                sim_t1: float, up_bytes: float, bcast_bytes: float) -> None:
    """Feed one completed round to an enabled probe.

    Only called when ``probe.enabled`` — everything here reads values the
    round already produced (RoundRecord, RoundResult stats), so disabled
    runs skip even the dict construction.
    """
    payload = dict(res.to_metrics())
    payload.update(acc=rec.acc, loss=rec.loss, upload_bytes=up_bytes,
                   broadcast_bytes=bcast_bytes,
                   wall_clock_s=sim_t1 - sim_t0)
    probe.metrics(payload, round=t)
    # the simulated round timeline: phase 1 (voting) then phase 2
    # (aggregation), on the cumulative sim clock
    st = res.stats or {}
    p1, p2 = st.get("phase1_s"), st.get("phase2_s")
    if p1 is not None:
        probe.sim_phase("phase1-vote", sim_t0, sim_t0 + float(p1), round=t)
        if p2 is not None:
            probe.sim_phase("phase2-aggregate", sim_t0 + float(p1),
                            sim_t0 + float(p1) + float(p2), round=t)
    else:
        probe.sim_phase("sim-round", sim_t0, sim_t1, round=t)
