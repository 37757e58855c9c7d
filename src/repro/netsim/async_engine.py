"""Async quorum-or-deadline aggregation engine (DESIGN.md §17).

The synchronous packet round (``netsim/batched.py``) is a lockstep: the
switch waits for every phase-2 uploader before closing, so one straggler
stalls the fleet.  This module is the FedBuff-style alternative: clients'
phase-1 votes and phase-2 payloads arrive on the *same* keyed packet
timelines, the switch folds value packets into the register bank as they
land (event order — sound because int32 addition is associative and
commutative mod 2^32), and the round closes on **quorum-or-deadline**:

* *quorum* — the round may close once ``quorum_frac`` of the announced
  uploaders have fully landed;
* *deadline* — with ``round_deadline_s`` set, the round closes at
  ``phase2_start + round_deadline_s`` even if the quorum is short.

Updates that straddle the close are **never dropped silently**: under
``late_policy="fold"`` a late update is carried (staleness-weighted) into
the next round's aggregate via the ``carry`` buffer; under ``"bounce"``
(or past the hard ``staleness_cap``) it returns to the client's
error-feedback residual, exactly as a non-uploader's would.  Staleness
weights are configurable: constant, polynomial decay ``(1+s)^-gamma``,
or constant-with-hard-cap.

The carry buffer — the partially-filled aggregation state — is an
explicit pytree threaded through ``RoundResult.state``, which the FL
loop already checkpoints round-granularly (``FLConfig.ckpt_path``), so
kill-and-resume reproduces the uninterrupted async history bit-exactly
with no new checkpoint machinery (DESIGN.md §14).

Correctness anchor, pinned by ``tests/test_async_engine.py`` and the
``benchmarks.async_throughput`` CI gate: with full quorum
(``quorum_frac=1``), no deadline and an empty carry, the async round is
**bit-identical** to the synchronous packet core — and therefore to
``aggregate_stack`` in the lossless full-participation configuration.
Every deviation from lockstep is a ``where``-selection away from the
literal synchronous expression, never an algebraic rewrite of it.

What stays host-side: round-close *policy* is resolved inside the traced
core (masks and ``where``-folds — async cells batch on the fleet axis);
the eager :class:`AsyncServer` reference mirrors the same close rules
through the shared :class:`~repro.serving.admission.AdmissionQueue` as
the oracle the traced computation is pinned against.

The registry side (:func:`aggregate_async_stack`, engine name
``"async"``) runs the in-memory stacked round with the register fold in
a randomized event order — bit-identical to ``aggregate_stack`` by int32
commutativity, so it inherits the engine-matrix oracle for free.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import compaction, engines
from repro.core.fediac import (FediACConfig, _block_compress_dense,
                               build_round_plan, client_vote_stack,
                               phase2_compress, plan_wants_dense_mask,
                               round_traffic, scatter_sum)
from repro.core.quantize import scale_factor
from repro.core.robust_agg import client_sum
from repro.core.shard_engine import shard_compress_stack
from repro.core.stream_engine import stream_compress_stack
from repro.serving.admission import AdmissionQueue
from repro.switch import n_packets
from repro.validate import (check_choice, check_finite_at_least,
                            check_interval, check_positive_finite, require)

from .batched import PACKET_DYN_FIELDS, packet_dyn, scale_num_table
from .dataplane import DataplaneStats, n_windows, slot_window
from .policies import (NetConfig, REGISTER_POLICIES, register_accumulate,
                       sample_participants, sample_stragglers)
from .timeline import (_masked_drain, deadline_mask, download_time,
                       lose_packets, mg1_departures, poisson_arrivals,
                       retransmit_delays)

__all__ = ["AsyncConfig", "ASYNC_DYN_FIELDS", "ASYNC_STAT_FIELDS",
           "STALENESS_MODES", "LATE_POLICIES", "make_async_packet_core",
           "async_packet_dyn", "init_async_carry", "aggregate_async_stack",
           "AsyncServer"]

#: staleness-weight schedules for updates that straddle the round close
STALENESS_MODES = ("constant", "poly", "cap")

#: what happens to an update that lands after the close (never: dropped)
LATE_POLICIES = ("fold", "bounce")

#: async-only aux scalars on top of the benign ones — the single source
#: of truth for downstream stat extraction (``PacketTransport`` folds
#: exactly these into its stats dict, mirroring ``CHAOS_STAT_FIELDS``).
ASYNC_STAT_FIELDS = ("late_folded", "late_bounced", "folded_in",
                     "staleness_s_sum", "buffer_occupancy", "carry_weight",
                     "quorum_met")

#: traced per-cell async knobs, appended to the benign PACKET_DYN_FIELDS —
#: cells differing only in these share one compiled async program.
ASYNC_DYN_FIELDS = PACKET_DYN_FIELDS + (
    "quorum_frac", "round_deadline_s", "staleness_weight",
    "staleness_gamma", "staleness_cap")

# fold_in constant deriving the event-order key of the in-memory engine;
# disjoint from the packet core's splits and §14's 7001-7100 fault keys.
_KEY_ARRIVAL = 7300


@dataclass(frozen=True)
class AsyncConfig(NetConfig):
    """A :class:`NetConfig` plus the quorum-or-deadline round-close policy
    (DESIGN.md §17).

    At the defaults — full quorum, no deadline, zero staleness pressure —
    the async core is bit-identical to the synchronous packet core.  The
    scalar knobs (``quorum_frac``, ``round_deadline_s``'s value,
    ``staleness_weight``/``gamma``/``cap``) are *dynamic* (traced per-cell
    on the fleet axis); ``staleness_mode``, ``late_policy``,
    ``register_policy`` and the *presence* of a deadline are structural
    and enter the batch signature.
    """

    # --- round close: the switch may close once ceil-rounded
    # quorum_frac * n_up uploaders have fully landed, and must close at
    # phase2_start + round_deadline_s if one is set (None = quorum only).
    quorum_frac: float = 1.0
    round_deadline_s: float | None = None

    # --- staleness-weighted merging of updates that straddle the close:
    # "constant" folds each late update at weight staleness_weight;
    # "poly" decays with relative staleness s as (1 + s) ** -gamma;
    # "cap" folds at staleness_weight while s <= staleness_cap and
    # bounces beyond it.
    staleness_mode: str = "constant"
    staleness_weight: float = 1.0
    staleness_gamma: float = 1.0
    staleness_cap: float = 4.0

    # --- what a late update becomes: "fold" carries it (weighted) into
    # the next round's aggregate; "bounce" returns it to the client's
    # error-feedback residual.  Neither drops it.
    late_policy: str = "fold"

    # --- how the register bank closes an overflowing window (§14).
    register_policy: str = "wrap"

    def __post_init__(self):
        super().__post_init__()
        check_interval("quorum_frac", self.quorum_frac, 0.0, 1.0,
                       lo_open=True)
        if self.round_deadline_s is not None:
            check_positive_finite("round_deadline_s", self.round_deadline_s)
        check_choice("staleness_mode", self.staleness_mode, STALENESS_MODES)
        check_interval("staleness_weight", self.staleness_weight, 0.0, 1.0,
                       lo_open=True)
        check_finite_at_least("staleness_gamma", self.staleness_gamma, 0.0)
        check_finite_at_least("staleness_cap", self.staleness_cap, 0.0)
        check_choice("late_policy", self.late_policy, LATE_POLICIES)
        check_choice("register_policy", self.register_policy,
                     REGISTER_POLICIES)
        require(self.n_leaves == 1, "n_leaves",
                "== 1 (the async engine closes rounds at a single switch; "
                "hierarchy support tracks ROADMAP item 3)", self.n_leaves)


def init_async_carry(d: int) -> dict:
    """The empty carry buffer: no pending late updates.  The pytree the
    FL loop checkpoints through ``agg_state`` (flat f32/int leaves — it
    round-trips the npz run state bit-exactly)."""
    return {"pending": jnp.zeros((int(d),), jnp.float32),
            "pending_w": jnp.zeros((), jnp.float32),
            "pending_n": jnp.zeros((), jnp.int32)}


def _inverse_permutation(order: jax.Array) -> jax.Array:
    return jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=order.dtype))


def _per_packet_departures(arrd: jax.Array, pkt_window: np.ndarray,
                           n_win: int, svc, not_before):
    """Windowed FIFO drain with *per-packet* departures.

    Replicates ``timeline.windowed_drain`` arithmetic bitwise (same sorted
    arrays, same mg1 recursion, same wait accounting) but keeps every
    packet's departure time instead of only the per-window completion —
    the event feed the quorum-or-deadline close consumes.  Returns
    ``(dep [N, P], completion, mean_wait, n_packets)``; masked (+inf)
    packets depart at +inf.
    """
    n = arrd.shape[0]
    svc = jnp.float32(svc)
    dep = jnp.full(arrd.shape, jnp.inf, jnp.float32)
    t_free = jnp.float32(not_before)
    wait_sum = jnp.float32(0.0)
    n_tot = jnp.int32(0)
    pkt_window = np.asarray(pkt_window)
    for w in range(int(n_win)):
        cols = np.flatnonzero(pkt_window == w)
        if cols.size == 0:
            continue
        a_w = jnp.maximum(arrd[:, cols], t_free).ravel()
        order = jnp.argsort(a_w)
        a_sorted = a_w[order]
        d_sorted = mg1_departures(a_sorted, svc, assume_sorted=True)
        dep = dep.at[:, cols].set(
            d_sorted[_inverse_permutation(order)].reshape(n, cols.size))
        live = jnp.isfinite(a_sorted)
        n_w = jnp.sum(live.astype(jnp.int32))
        waits = jnp.where(live, d_sorted - a_sorted - svc, 0.0)
        mean_w = jnp.sum(waits) / jnp.maximum(n_w, 1)
        t_free = jnp.where(n_w > 0,
                           jnp.max(jnp.where(live, d_sorted, -jnp.inf)),
                           t_free)
        wait_sum = wait_sum + mean_w * n_w
        n_tot = n_tot + n_w
    return dep, t_free, wait_sum / jnp.maximum(n_tot, 1), n_tot


def make_async_packet_core(cfg: FediACConfig, net: AsyncConfig,
                           n_clients: int):
    """Build the traced async FediAC packet round.

    Contract mirrors :func:`repro.netsim.batched.make_fediac_packet_core`
    with the carry buffer threaded through:
    ``core(u_stack, carry, key, net_key, round_idx, rates, dyn)`` returns
    ``(delta, residuals, aux, new_carry)``.  ``dyn`` is the benign dict
    extended by the :data:`ASYNC_DYN_FIELDS` knobs
    (:func:`async_packet_dyn`).  Phase 1, the GIA and phase-2 compression
    are the benign core's expressions verbatim; only the *close* differs:
    per-client completion events from the windowed FIFO drain, a
    quorum-or-deadline ``t_close``, and staleness-weighted folding of the
    stragglers through the carry.

    ``aux`` keeps every benign key (``n_up`` reports the *committed*
    on-time uploader count; ``n_up_wire`` the announced one that priced
    the wire bytes) plus the :data:`ASYNC_STAT_FIELDS` extras and the
    per-client ``t_done`` / scalar ``t_close`` event times the oracle
    tests consume.
    """
    spec = engines.resolve(cfg)
    cfg = engines.with_pallas(cfg, spec)   # the spec's Pallas choice
    n = int(n_clients)
    stream = spec.name == "stream"
    sharded = spec.name == "sharded"
    topk = cfg.compact_mode != "block"
    slowdown = float(net.straggler_slowdown)
    f_num = jnp.asarray(scale_num_table(cfg.bits, n))
    bounce_all = net.late_policy == "bounce"

    def core(u_stack, carry, key, net_key, round_idx, rates, dyn):
        n_, d = u_stack.shape
        assert n_ == n, (n_, n)
        n_chunks = d // cfg.vote_chunk
        tr = round_traffic(cfg, d)
        p1_pkts = n_packets(tr.phase1_bytes, net.mtu)
        gia_pkts = n_packets(-(-n_chunks // 8), net.mtu)
        cov = -(-n_chunks // p1_pkts)
        pkt_of_chunk = np.minimum(np.arange(n_chunks) // cov, p1_pkts - 1)

        rk = jax.random.fold_in(net_key, round_idx)
        k_part, k_strag, k_arr1, k_loss1, k_arr2, k_retx = \
            jax.random.split(rk, 6)
        keys = jax.random.split(key, 2 * n)
        vote_keys, q_keys = keys[:n], keys[n:]

        # ---- phase 1: byte-for-byte the synchronous core.
        part = sample_participants(k_part, n, dyn["participation"])
        strag = sample_stragglers(k_strag, part, dyn["straggler_frac"])
        slow = jnp.where(strag, jnp.float32(slowdown), 1.0)
        train_s = jnp.float32(dyn["local_train_s"]) * slow
        eff_rates = jnp.asarray(rates, jnp.float32) / slow
        svc = jnp.float32(dyn["svc"])

        arr1 = poisson_arrivals(k_arr1, eff_rates, p1_pkts, train_s)
        deliv = lose_packets(k_loss1, arr1.shape, dyn["loss"])
        deliv = deliv & part[:, None]
        if net.vote_deadline_s is not None:
            deliv = deliv & deadline_mask(arr1, net.vote_deadline_s)
        chunk_ok = deliv[:, pkt_of_chunk]
        votes = client_vote_stack(u_stack, cfg, vote_keys)
        counts = jnp.sum(votes.astype(jnp.int32) * chunk_ok.astype(jnp.int32),
                         axis=0)
        st1 = _masked_drain(jnp.where(deliv, arr1, jnp.inf), svc)
        t1 = jnp.where(st1.n_packets > 0, st1.completion_s,
                       jnp.max(jnp.where(part, train_s, -jnp.inf)))
        if net.vote_deadline_s is not None:
            t1 = jnp.maximum(t1, jnp.float32(net.vote_deadline_s))

        voter = chunk_ok.any(axis=1)
        up = (part & voter) if net.drop_late_voters else part
        n_up = jnp.sum(up.astype(jnp.int32))
        t_gia = download_time(gia_pkts, rates)

        # ---- GIA + phase-2 compress: the benign expressions verbatim.
        m = jnp.max(jnp.where(up[:, None], jnp.abs(u_stack), 0.0))
        f = f_num[n_up] / jnp.clip(m, 1e-12, None)
        a = dyn["a_table"][n_up]
        plan = build_round_plan(counts, cfg, n, a=a,
                                with_dense_mask=(plan_wants_dense_mask(cfg)
                                                 or ((stream or sharded)
                                                     and topk)),
                                with_slot_map=(stream or sharded) and topk)
        if stream:
            q_bufs, res = stream_compress_stack(u_stack, cfg, f, q_keys, plan)
        elif sharded:
            q_bufs, res = shard_compress_stack(
                u_stack, cfg, f, q_keys, plan,
                devices=spec.devices or None, axis=spec.axis)
        else:
            compress = phase2_compress(cfg)
            q_bufs, res = jax.vmap(
                lambda uu, kk: compress(uu, cfg, f, kk, plan))(u_stack, q_keys)

        # ---- phase-2 event feed: the synchronous windowed drain, kept at
        # per-packet granularity.  Arrival tensors, ARQ delays and window
        # maps are identical to reliable_upload's; only the *read-out*
        # (per-client completion events instead of one completion scalar)
        # is new, so the zero-pressure timeline is bitwise unchanged.
        c_live = q_bufs.shape[1]
        live = max(int(c_live), 1)
        n_win = n_windows(live, net.memory_slots)
        pkts = n_packets(tr.phase2_bytes, net.mtu)
        slots_per_pkt = -(-live // pkts)
        pkt_window = np.minimum((np.arange(pkts) * slots_per_pkt)
                                // net.memory_slots, n_win - 1)
        start2 = t1 + t_gia
        arr2 = poisson_arrivals(k_arr2, eff_rates, pkts, start2)
        delay, retx = retransmit_delays(k_retx, arr2.shape, dyn["loss"],
                                        net.rto_s, net.max_retries)
        arrd = jnp.where(up[:, None], arr2 + delay, jnp.inf)
        retx = jnp.where(up[:, None], retx, 0)
        dep, completion, mean_wait, _ = _per_packet_departures(
            arrd, pkt_window, n_win, svc, start2)
        t_done = jnp.max(dep, axis=1)      # +inf for non-uploaders

        # ---- quorum-or-deadline close.
        qn = jnp.clip(jnp.round(jnp.float32(dyn["quorum_frac"])
                                * n_up.astype(jnp.float32)).astype(jnp.int32),
                      1, jnp.maximum(n_up, 1))
        t_quorum = jnp.sort(jnp.where(up, t_done, jnp.inf))[qn - 1]
        if net.round_deadline_s is not None:
            t_deadline = start2 + jnp.float32(dyn["round_deadline_s"])
            t_close = jnp.minimum(t_quorum, t_deadline)
            quorum_met = (t_quorum <= t_deadline).astype(jnp.int32)
        else:
            t_close = t_quorum
            quorum_met = jnp.int32(1)
        on_time = up & (t_done <= t_close)
        late = up & ~on_time
        n_on = jnp.sum(on_time.astype(jnp.int32))

        # ---- staleness weights for the stragglers.
        s = (t_done - t_close) / jnp.maximum(t_close, jnp.float32(1e-9))
        if net.staleness_mode == "poly":
            w = (1.0 + jnp.maximum(s, 0.0)) ** (-jnp.float32(
                dyn["staleness_gamma"]))
        else:
            w = jnp.broadcast_to(jnp.float32(dyn["staleness_weight"]),
                                 s.shape)
        fold_ok = jnp.zeros_like(late) if bounce_all else late
        if net.staleness_mode == "cap" and not bounce_all:
            fold_ok = fold_ok & (s <= jnp.float32(dyn["staleness_cap"]))
        late_fold = late & fold_ok
        late_bounce = late & ~fold_ok
        w_late = jnp.where(late_fold, w, 0.0)

        # ---- close the register bank over the on-time rows (§14 overflow
        # policies); wrap is bitwise the masked jnp.sum of the sync core.
        rows = jnp.where(on_time[:, None], q_bufs, 0)
        summed, reg_ovf, reg_shift = register_accumulate(
            rows, policy=net.register_policy,
            slot_window=slot_window(c_live, net.memory_slots),
            n_windows=n_win)
        if net.register_policy == "rescale":
            summed = summed.astype(jnp.float32) * jnp.exp2(
                reg_shift.astype(jnp.float32))
        n_on_safe = jnp.maximum(n_on, 1)
        if cfg.compact_mode == "block":
            scat = compaction.block_scatter(
                summed, plan.keep_dense, plan.pos, d, cfg.block_size,
                cfg.capacity_frac).astype(jnp.float32)
        else:
            scat = scatter_sum(summed, plan.idx, plan.keep, cfg,
                               d).astype(jnp.float32)
        # base is literally the synchronous formula; the carry fold is a
        # where-selection away from it, so an empty carry costs nothing in
        # bit-identity (never relies on x + 0.0 == x).
        base = scat / (n_on_safe * f)
        pending_in = jnp.asarray(carry["pending"], jnp.float32)
        w_in = jnp.asarray(carry["pending_w"], jnp.float32)
        n_in = jnp.asarray(carry["pending_n"], jnp.int32)
        has_carry = n_in > 0
        folded = (scat / f + pending_in) / jnp.maximum(
            n_on.astype(jnp.float32) + w_in, jnp.float32(1e-9))
        delta = jnp.where(has_carry, folded, base)
        applied = (n_on > 0) | has_carry
        delta = jnp.where(applied, delta, 0.0)

        # ---- late folds feed the next round's carry (already in update
        # units: dequantized by this round's f, staleness-weighted).
        late_buf = jnp.sum(q_bufs.astype(jnp.float32) * w_late[:, None],
                           axis=0)
        if cfg.compact_mode == "block":
            late_scat = compaction.block_scatter(
                late_buf, plan.keep_dense, plan.pos, d, cfg.block_size,
                cfg.capacity_frac)
        else:
            late_scat = scatter_sum(late_buf, plan.idx, plan.keep, cfg, d)
        n_fold = jnp.sum(late_fold.astype(jnp.int32))
        new_carry = {"pending": late_scat.astype(jnp.float32) / f,
                     "pending_w": jnp.sum(w_late),
                     "pending_n": n_fold}

        # an on-time or folded client's update is in flight (aggregate or
        # carry) — its residual advances; a bounced one keeps its whole
        # update as residual, like a non-uploader.
        keep_upd = on_time | late_fold
        residuals = jnp.where(keep_upd[:, None], res, u_stack)

        # ---- clocks and accounting (benign formulas over the close).
        t2 = jnp.where(n_up > 0, jnp.maximum(t_close, start2), start2)
        wall2 = t2 + download_time(pkts, rates)
        wall = jnp.where(applied, wall2, start2)
        n_part = jnp.sum(part.astype(jnp.int32))
        delivered_chunks = jnp.sum(chunk_ok.astype(jnp.int32))
        value_ops = jnp.maximum(n_on - 1, 0) * c_live
        aux = {
            "participants": part, "stragglers": strag, "uploaders": on_time,
            "counts": counts,
            "n_part": n_part, "n_up": n_on, "n_up_wire": n_up,
            "n_strag": jnp.sum(strag.astype(jnp.int32)),
            "votes_lost": n_part * p1_pkts
                          - jnp.sum(deliv.astype(jnp.int32)),
            "retransmissions": jnp.sum(retx),
            "retx_last": jnp.sum(retx[:, -1]),
            "wall_clock_s": wall, "phase1_s": t1,
            "phase2_s": t2 - t1,
            "mean_wait_s": mean_wait,
            "aggregation_ops": delivered_chunks + jnp.where(n_on > 0,
                                                            value_ops, 0),
            "peak_live_slots": jnp.where(n_on > 0,
                                         min(net.memory_slots, c_live), 0),
            "passes": jnp.int32(n_win),
            # async extras (ASYNC_STAT_FIELDS + the event times the
            # AsyncServer oracle is pinned against)
            "late_folded": n_fold,
            "late_bounced": jnp.sum(late_bounce.astype(jnp.int32)),
            "folded_in": n_in,
            "staleness_s_sum": jnp.sum(jnp.where(late, s, 0.0)),
            "buffer_occupancy": n_fold,
            "carry_weight": jnp.sum(w_late),
            "quorum_met": quorum_met,
            "overflow_slots": jnp.sum(reg_ovf.astype(jnp.int32)),
            "t_done": t_done, "t_close": t_close,
        }
        return delta, residuals, aux, new_carry

    return core


def async_packet_dyn(cfg: FediACConfig, net: AsyncConfig, n_clients: int,
                     local_train_s: float, svc: float) -> dict:
    """The traced ``dyn`` dict of one async scenario: the benign
    :func:`~repro.netsim.batched.packet_dyn` scalars plus the round-close
    knobs, in :data:`ASYNC_DYN_FIELDS` order."""
    dyn = packet_dyn(cfg, net, n_clients, local_train_s, svc)
    dyn.update({
        "quorum_frac": jnp.float32(net.quorum_frac),
        "round_deadline_s": jnp.float32(net.round_deadline_s
                                        if net.round_deadline_s is not None
                                        else 0.0),
        "staleness_weight": jnp.float32(net.staleness_weight),
        "staleness_gamma": jnp.float32(net.staleness_gamma),
        "staleness_cap": jnp.float32(net.staleness_cap),
    })
    return dyn


# ---------------------------------------------------------------------------
# The in-memory "async" engine (core/engines.py registry)
# ---------------------------------------------------------------------------

def _event_fold(rows: jax.Array) -> jax.Array:
    """Fold rows into the register bank one event at a time (lax.scan) —
    the switch's incremental accumulation.  Bitwise ``rows.sum(axis=0)``
    for integer rows (associative + commutative mod 2^32)."""
    def step(acc, row):
        return acc + row, None
    acc, _ = jax.lax.scan(step, jnp.zeros(rows.shape[1:], rows.dtype), rows)
    return acc


def aggregate_async_stack(u_stack: jax.Array, cfg: FediACConfig,
                          key: jax.Array, *, a=None):
    """One stacked FediAC round with event-ordered incremental folding.

    Same signature and return contract as
    :func:`repro.core.fediac.aggregate_stack` — and bit-identical to it:
    clients' phase-2 buffers arrive in a randomized order (a deterministic
    permutation drawn from ``fold_in(key, 7300)``) and fold into the bank
    one at a time, which equals the batch ``sum(axis=0)`` exactly because
    int32 addition is associative and commutative mod 2^32.  Registered
    as engine ``"async"``, so it inherits the engine-matrix oracle.
    """
    n, d = u_stack.shape
    keys = jax.random.split(key, 2 * n)
    vote_keys, q_keys = keys[:n], keys[n:]
    # summing the per-client vote rows is pinned bit-identical to the
    # batch-level _vote_counts_stack (see client_vote_stack's contract)
    counts = client_vote_stack(u_stack, cfg,
                               vote_keys).astype(jnp.int32).sum(axis=0)
    m = jnp.max(jnp.abs(u_stack))
    f = scale_factor(cfg.bits, n, 1.0) / jnp.clip(m, 1e-12, None)
    plan = build_round_plan(counts, cfg, n, a=a,
                            with_dense_mask=plan_wants_dense_mask(cfg))
    perm = jnp.argsort(jax.random.uniform(
        jax.random.fold_in(key, _KEY_ARRIVAL), (n,)))
    # The §18 order-statistic close is a barrier: the bank buffers every
    # client's slot values before closing, so in robust mode the
    # incremental fold degenerates to fold-then-close through the shared
    # client_sum seam (over the unpermuted stack — the stable tie-break
    # is by client index, not arrival order).  Sum mode keeps the event
    # fold verbatim (Python-gated).
    def close(q):
        if cfg.robust_agg == "sum":
            return _event_fold(jnp.take(q, perm, axis=0)), n
        return client_sum(q, cfg)

    if cfg.compact_mode == "block":
        q_dense, residuals = jax.vmap(
            lambda u, k: _block_compress_dense(u, cfg, f, k, plan))(u_stack,
                                                                    q_keys)
        summed, kept = close(q_dense)
        delta = jnp.where(plan.keep_dense, summed,
                          0).astype(jnp.float32) / (kept * f)
        return delta, residuals, counts, round_traffic(cfg, d)
    compress = phase2_compress(cfg)
    q_bufs, residuals = jax.vmap(
        lambda u, k: compress(u, cfg, f, k, plan))(u_stack, q_keys)
    summed, kept = close(q_bufs)
    delta = scatter_sum(summed, plan.idx, plan.keep, cfg,
                        d).astype(jnp.float32) / (kept * f)
    return delta, residuals, counts, round_traffic(cfg, d)


# ---------------------------------------------------------------------------
# Eager host-side reference: the round-close state machine on the shared
# admission queue (the oracle tests pin the traced close against)
# ---------------------------------------------------------------------------

class AsyncServer:
    """Event-driven round-close reference over an
    :class:`~repro.serving.admission.AdmissionQueue` slot pool.

    The traced core resolves the quorum-or-deadline close as fixed-shape
    mask algebra; this class is the same state machine run eagerly, one
    completion event at a time — on-time updates fold and free their slot
    immediately, late folds occupy a slot (the carry buffer) until the
    *next* round consumes them, bounces never admit.  Slot occupancy and
    the late-fold/late-bounce counters land in a
    :class:`~repro.netsim.dataplane.DataplaneStats`, exercising the same
    stat fields the traced path reports.
    """

    def __init__(self, net: AsyncConfig, n_slots: int = 64):
        self.net = net
        self.queue = AdmissionQueue(n_slots)
        self.stats = DataplaneStats(passes=0)

    def close_time(self, t_done: np.ndarray, start: float) -> float:
        """Quorum-or-deadline close of one round's completion events
        (``+inf`` = absent client) — the host mirror of the traced rule."""
        t = np.asarray(t_done, np.float32)
        finite = np.isfinite(t)
        n_up = int(finite.sum())
        if n_up == 0:
            t_quorum = np.inf
        else:
            qn = min(max(1, round(self.net.quorum_frac * n_up)), n_up)
            t_quorum = float(np.sort(t[finite])[qn - 1])
        if self.net.round_deadline_s is None:
            return t_quorum
        return min(t_quorum, float(start) + self.net.round_deadline_s)

    def run_round(self, t_done: np.ndarray, start: float = 0.0) -> dict:
        """Process one round's completion events in time order.

        Returns ``{"t_close", "on_time", "late_fold", "late_bounce",
        "folded_in", "occupancy"}``; ``folded_in`` is the number of
        carried-over updates from earlier rounds consumed at this close.
        """
        t = np.asarray(t_done, np.float32)
        t_close = self.close_time(t, start)
        folded_in = self.queue.n_active
        for slot, _ in list(self.queue.active()):
            self.queue.release(slot)      # carried updates fold at close
        on_time = np.isfinite(t) & (t <= t_close)
        late = np.isfinite(t) & ~on_time
        s = (t - t_close) / max(t_close, 1e-9)
        if self.net.late_policy == "bounce":
            fold_ok = np.zeros_like(late)
        elif self.net.staleness_mode == "cap":
            fold_ok = late & (s <= self.net.staleness_cap)
        else:
            fold_ok = late
        late_fold = late & fold_ok
        late_bounce = late & ~fold_ok
        for i in np.argsort(t, kind="stable"):
            if not np.isfinite(t[i]) or not late_fold[i]:
                continue
            self.queue.submit(int(i))
            self.queue.admit()            # occupies a slot until next close
        self.stats = self.stats.merge(DataplaneStats(
            passes=0, late_folds=int(late_fold.sum()),
            late_bounces=int(late_bounce.sum())))
        return {"t_close": t_close, "on_time": on_time,
                "late_fold": late_fold, "late_bounce": late_bounce,
                "folded_in": folded_in,
                "occupancy": self.queue.n_active}


def _run_async_engine(spec, u_stack, cfg, key, a):
    return aggregate_async_stack(u_stack, cfg, key, a=a)
