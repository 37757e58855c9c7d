"""Chaos dataplane: deterministic fault injection for the packet round
(DESIGN.md §14).

:class:`FaultConfig` extends :class:`~repro.netsim.policies.NetConfig`
with the failure modes a real in-network aggregation point concentrates —
bursty (Gilbert–Elliott) link loss, clients crashing mid-round, ACK loss
producing duplicate retransmissions, packet reordering, and register-bank
faults (int32 overflow, window resets) — together with the graceful-
degradation policies that keep the round *correct* under them: sequence-
numbered duplicate suppression, saturate/rescale register closing, and
quorum-or-abort round retry with bounded exponential backoff on the
simulated clock.

Every fault draw derives from the same per-round threefry key the benign
policies use (``net_round_key(seed, round_idx)``), folded at disjoint
constants, so a faulty round is as replayable as a clean one.  All fault
models are *fixed-shape* mask algebra over the existing ``[N, P]`` packet
tensors — no data-dependent shapes — so fault cells ride the fleet's
``jit(vmap)`` axis exactly like benign cells (``sweep/fleet.py``), with
the per-cell fault rates entering as traced scalars via ``dyn``.

The central invariant, pinned by tests and the ``benchmarks.faults`` CI
gate: with every fault knob at its zero default the chaos core is
**bit-identical** to :func:`repro.netsim.batched.make_fediac_packet_core`.
Each fault model is built to make that structural rather than incidental:

* Gilbert–Elliott reuses the plain path's per-packet loss uniforms and
  only modulates the *threshold* they are compared against (a separate
  key drives the channel-state chain), so ``ge_p_gb == 0`` degenerates
  bitwise to i.i.d. ``lose_packets``;
* crash / duplicate / reset effects are ``where``-masks that are the
  identity when their rate is zero, and duplicate packets are extra
  ``+inf``-arrival columns, which the drain's finite-masked statistics
  provably ignore;
* reordering jitter adds ``uniform * 0.0 == +0.0`` at rate zero;
* the register-bank scan's wrap mode equals ``jnp.sum`` bitwise (int32
  addition is associative mod 2^32), so the overflow *flag* is free;
* quorum retry and the consensus floor are Python-gated on their zero
  defaults — the clean program is not merely equal, it is unchanged.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import compaction, engines
from repro.core.fediac import (FediACConfig, build_round_plan,
                               client_vote_stack, phase2_compress,
                               plan_wants_dense_mask, round_traffic,
                               scatter_sum)
from repro.core.shard_engine import shard_compress_stack
from repro.core.stream_engine import stream_compress_stack
from repro.switch import n_packets
from repro.validate import (check_at_least, check_choice,
                            check_finite_at_least, check_interval, require)

from .batched import (PACKET_DYN_FIELDS, packet_dyn, scale_num_table)
from .dataplane import n_windows, slot_window
from .hierarchy import drain_hierarchy, leaf_assignment
from .policies import (BackoffPolicy, NetConfig, REGISTER_POLICIES,
                       register_accumulate, sample_participants,
                       sample_stragglers)
from .timeline import (_masked_drain, deadline_mask, download_time,
                       poisson_arrivals, retransmit_delays)

__all__ = ["FaultConfig", "FAULT_DYN_FIELDS", "CHAOS_STAT_FIELDS",
           "make_chaos_packet_core", "chaos_packet_dyn",
           "gilbert_elliott_stationary"]

#: the chaos-only aux scalars the core returns on top of the benign ones —
#: the single source of truth for downstream stat extraction
#: (``PacketTransport`` folds exactly these into its stats dict).
CHAOS_STAT_FIELDS = ("crashed", "duplicates", "resets", "overflow_slots",
                     "aborted", "attempts")

#: traced per-cell fault rates, appended to the benign PACKET_DYN_FIELDS —
#: cells differing only in these share one compiled chaos program.
FAULT_DYN_FIELDS = PACKET_DYN_FIELDS + (
    "ge_p_gb", "ge_p_bg", "ge_loss_bad", "crash_rate", "crash_p2_frac",
    "dup_rate", "reorder_jitter_s", "reg_reset_rate", "backoff_s")

# fold_in constants deriving the fault keys from the round key.  Disjoint
# from the plain core's 6-way split of the same key, so adding a fault
# model never perturbs the benign draws.
_KEY_GE = 7001        # Gilbert–Elliott channel-state transitions
_KEY_CRASH = 7002     # who crashes, in which phase, how far in
_KEY_DUP = 7003       # ACK-loss duplicate deliveries
_KEY_JITTER = 7004    # reordering jitter
_KEY_RESET = 7005     # register-bank window resets
_KEY_RETRY = 7100     # + attempt index: quorum retry re-draws


@dataclass(frozen=True)
class FaultConfig(NetConfig):
    """A :class:`NetConfig` plus deterministic fault models and the
    degradation policies that answer them (DESIGN.md §14).

    All rates default to zero / the benign policy, at which point the
    chaos core is bit-identical to the plain packet core.  The rate
    fields are *dynamic* (traced per-cell scalars on the fleet axis);
    ``dedup``, ``register_policy``, ``quorum_floor`` and ``round_retries``
    are structural and enter the batch signature.
    """

    # --- Gilbert–Elliott bursty loss (phase-1 vote packets).  A two-state
    # channel per client: good state loses packets at the base ``loss``,
    # bad state at ``ge_loss_bad``; ``ge_p_gb``/``ge_p_bg`` are the per-
    # packet good->bad / bad->good transition probabilities.  Phase 2 keeps
    # the i.i.d. per-attempt ARQ model (its persistent retransmission
    # already absorbs bursts as repeated attempts).
    ge_p_gb: float = 0.0
    ge_p_bg: float = 0.5
    ge_loss_bad: float = 1.0

    # --- client crash mid-round: a crashed client emits a strict prefix of
    # its packets and is excluded from the aggregate for the round.
    # ``crash_p2_frac`` splits crashes between phase 1 (votes cut short,
    # client sits out phase 2) and phase 2 (votes counted — the GIA was
    # already broadcast — but the value upload aborts partway; the switch
    # commits none of its slots, all-or-nothing).
    crash_rate: float = 0.0
    crash_p2_frac: float = 0.5

    # --- ACK loss: the client re-sends a delivered packet one RTO later.
    # With ``dedup`` (sequence-numbered suppression) the duplicate only
    # costs wire bytes and drain time; without it the duplicate deposits
    # into the register bank a second time (the double-count FediAC's
    # phase 2 must never admit — modeled so the test suite can pin the
    # difference).
    dup_rate: float = 0.0
    dedup: bool = True

    # --- packet reordering: uniform [0, reorder_jitter_s) added per
    # phase-2 packet arrival (the drain sorts, so jitter reorders service).
    reorder_jitter_s: float = 0.0

    # --- register-bank faults: how an int32 register window closes when
    # its true sum leaves the representable range ("wrap" is hardware
    # default; "saturate"/"rescale" are the degradation policies — see
    # policies.register_accumulate), and a per-window reset probability
    # (a reset window's packets are replayed one RTO later; idempotent
    # under dedup).
    register_policy: str = "wrap"
    reg_reset_rate: float = 0.0

    # --- quorum-or-abort: if fewer than ``quorum_floor`` uploaders
    # survive phase 1, the round re-runs its network phase (fresh draws,
    # same votes) after an exponential backoff ``backoff_s * 2^attempt``
    # on the simulated clock, up to ``round_retries`` retries; if every
    # attempt fails the round aborts — no aggregate is applied, the time
    # is still spent.  0 disables (benign single-attempt program).
    quorum_floor: int = 0
    round_retries: int = 2
    backoff_s: float = 0.1

    def __post_init__(self):
        super().__post_init__()
        for name in ("ge_p_gb", "ge_p_bg", "ge_loss_bad", "crash_rate",
                     "crash_p2_frac", "dup_rate", "reg_reset_rate"):
            check_interval(name, getattr(self, name), 0.0, 1.0)
        require(not (self.ge_p_gb > 0.0 and self.ge_p_bg <= 0.0),
                "ge_p_bg", "> 0 when ge_p_gb > 0 (the bad state must be "
                "escapable or the chain absorbs)", self.ge_p_bg)
        check_finite_at_least("reorder_jitter_s", self.reorder_jitter_s, 0.0)
        check_choice("register_policy", self.register_policy,
                     REGISTER_POLICIES)
        check_at_least("quorum_floor", self.quorum_floor, 0)
        check_at_least("round_retries", self.round_retries, 0)
        check_finite_at_least("backoff_s", self.backoff_s, 0.0)

    def retry_policy(self) -> BackoffPolicy:
        """The quorum-retry clock as a :class:`BackoffPolicy`: exponential
        doubling from ``backoff_s``, ``round_retries`` bounded."""
        return BackoffPolicy(base_s=self.backoff_s, factor=2.0,
                             max_retries=self.round_retries)


def gilbert_elliott_stationary(p_gb: float, p_bg: float) -> float:
    """Stationary bad-state probability of the two-state chain — the
    property tests compare the empirical marginal loss against
    ``(1 - pi) * loss + pi * loss_bad``."""
    if p_gb <= 0.0:
        return 0.0
    return p_gb / (p_gb + p_bg)


def _ge_loss_probability(key, shape, loss, p_gb, p_bg, loss_bad):
    """[N, P] per-packet loss probability under the Gilbert–Elliott chain.

    The chain (one per client, started in the good state) is driven by its
    own uniforms; the *delivery* comparison reuses the caller's loss
    uniforms, so at ``p_gb == 0`` every packet sees exactly
    ``float32(loss)`` and delivery is bitwise ``lose_packets``.
    """
    u = jax.random.uniform(key, shape)

    def step(bad, u_col):
        bad = jnp.where(bad, u_col >= jnp.float32(p_bg),
                        u_col < jnp.float32(p_gb))
        return bad, bad

    _, states = jax.lax.scan(step, jnp.zeros((shape[0],), bool), u.T)
    return jnp.where(states.T, jnp.float32(loss_bad), jnp.float32(loss))


def _chaos_upload(k_arr, k_retx, k_dup, k_jit, k_reset, rates_rows, start,
                  live_slots: int, wire_bytes: int, leaf_of, svc, *,
                  loss, rto_s, max_retries: int, memory_slots: int,
                  n_leaves: int, mtu: int, not_before, up, crash_p2,
                  cut_frac, dup_rate, jitter_s, reset_rate):
    """Phase-2 reliable upload with crash prefixes, duplicates, jitter and
    register-window resets — :func:`repro.netsim.batched.reliable_upload`
    plus the fault mask algebra.

    Duplicates (ACK loss, and every packet of a reset window) are modeled
    as one extra copy of the packet arriving an RTO later: an extra
    ``[N, P]`` block of columns concatenated to the arrival tensor, +inf
    where no duplicate exists, so the clean round drains the identical
    finite packet set.  Returns ``(DrainStats, n_retx, retx_last, n_win,
    dup_slot bool[N, live], n_dup, n_reset)`` — crash-prefix and duplicate
    packets fold into the retransmission counts, which is what makes the
    existing byte-pricing code exact without modification (a crasher's
    prefix is all full-MTU packets: the cut is strictly before the final
    partial packet, so ``retx_last`` needs no crash term).
    """
    live = max(int(live_slots), 1)
    n_win = n_windows(live, memory_slots)
    pkts = n_packets(wire_bytes, mtu)
    slots_per_pkt = -(-live // pkts)
    pkt_window = np.minimum((np.arange(pkts) * slots_per_pkt)
                            // memory_slots, n_win - 1)
    pkt_of_slot = np.minimum(np.arange(live) // slots_per_pkt, pkts - 1)
    arr = poisson_arrivals(k_arr, rates_rows, pkts, start)
    delay, retx = retransmit_delays(k_retx, arr.shape, loss, rto_s,
                                    max_retries)
    jit_d = jax.random.uniform(k_jit, arr.shape) * jnp.float32(jitter_s)
    cut = jnp.floor(jnp.float32(cut_frac) * pkts).astype(jnp.int32)
    pkt_idx = jnp.arange(pkts, dtype=jnp.int32)
    emit = up[:, None] & jnp.where(crash_p2[:, None],
                                   pkt_idx[None, :] < cut[:, None], True)
    arrd = jnp.where(emit, arr + delay + jit_d, jnp.inf)
    retx = jnp.where(emit, retx, 0)
    dup = jax.random.uniform(k_dup, arr.shape) < jnp.float32(dup_rate)
    reset = jax.random.uniform(k_reset, (n_win,)) < jnp.float32(reset_rate)
    dup = (dup | reset[pkt_window][None, :]) & emit
    dup_arr = jnp.where(dup, arrd + jnp.float32(rto_s), jnp.inf)
    all_arr = jnp.concatenate([arrd, dup_arr], axis=1)
    fwd = n_packets(min(memory_slots, live) * 4, mtu)
    st = drain_hierarchy(all_arr, leaf_of,
                         np.concatenate([pkt_window, pkt_window]),
                         n_win, n_leaves, svc, fwd, not_before=not_before)
    crash_pkts = jnp.sum(jnp.where(up & crash_p2, cut, 0))
    n_dup = jnp.sum(dup.astype(jnp.int32))
    n_retx = jnp.sum(retx) + n_dup + crash_pkts
    retx_last = jnp.sum(retx[:, -1]) + jnp.sum(dup[:, -1].astype(jnp.int32))
    return (st, n_retx, retx_last, n_win, dup[:, pkt_of_slot], n_dup,
            jnp.sum(reset.astype(jnp.int32)))


def make_chaos_packet_core(cfg: FediACConfig, net: FaultConfig,
                           n_clients: int):
    """Build the traced fault-injected FediAC packet round.

    Same contract as :func:`repro.netsim.batched.make_fediac_packet_core`
    — ``core(u_stack, key, net_key, round_idx, rates, dyn)`` returning
    ``(delta, residuals, aux)`` — with ``dyn`` extended by the
    :data:`FAULT_DYN_FIELDS` rates (:func:`chaos_packet_dyn`), so clean
    and faulty cells of one structural configuration batch through one
    compiled program.  ``aux`` keeps every plain-core key with the same
    accounting semantics (crash prefixes, duplicates and reset replays
    fold into ``retransmissions``; failed quorum attempts fold into
    ``n_part``; ``n_up`` reports the *committed* uploader count) plus the
    chaos extras ``crashed`` / ``duplicates`` / ``resets`` /
    ``overflow_slots`` / ``aborted`` / ``attempts``.
    """
    spec = engines.resolve(cfg)
    cfg = engines.with_pallas(cfg, spec)   # the spec's Pallas choice
    n = int(n_clients)
    stream = spec.name == "stream"
    sharded = spec.name == "sharded"
    topk = cfg.compact_mode != "block"
    leaf_of = leaf_assignment(n, net.n_leaves)
    slowdown = float(net.straggler_slowdown)
    f_num = jnp.asarray(scale_num_table(cfg.bits, n))
    quorum = net.quorum_floor > 0
    n_attempts = (int(net.round_retries) + 1) if quorum else 1

    def core(u_stack, key, net_key, round_idx, rates, dyn):
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
        votes = client_vote_stack(u_stack, cfg, vote_keys)
        votes_i32 = votes.astype(jnp.int32)

        def phase1_attempt(ks):
            """One network phase 1: sampling, vote packets under the GE
            channel, crash draws, the quorum deadline — everything up to
            (but not including) the GIA.  Pure in its six keys so the
            quorum policy can re-run it with fresh draws."""
            kp, kst, ka1, kl1, kge, kcr = ks
            part = sample_participants(kp, n, dyn["participation"])
            strag = sample_stragglers(kst, part, dyn["straggler_frac"])
            slow = jnp.where(strag, jnp.float32(slowdown), 1.0)
            train_s = jnp.float32(dyn["local_train_s"]) * slow
            eff_rates = jnp.asarray(rates, jnp.float32) / slow
            arr1 = poisson_arrivals(ka1, eff_rates, p1_pkts, train_s)
            loss_p = _ge_loss_probability(
                kge, arr1.shape, dyn["loss"], dyn["ge_p_gb"],
                dyn["ge_p_bg"], dyn["ge_loss_bad"])
            deliv = jax.random.uniform(kl1, arr1.shape) >= loss_p
            kc, kph, kcut = jax.random.split(kcr, 3)
            crashed = jax.random.uniform(kc, (n,)) < dyn["crash_rate"]
            in_p2 = jax.random.uniform(kph, (n,)) < dyn["crash_p2_frac"]
            crash_p1 = crashed & ~in_p2
            crash_p2 = crashed & in_p2
            u_cut = jax.random.uniform(kcut, (n, 2))
            cut1 = jnp.floor(u_cut[:, 0] * p1_pkts).astype(jnp.int32)
            pkt_idx = jnp.arange(p1_pkts, dtype=jnp.int32)
            deliv = deliv & jnp.where(crash_p1[:, None],
                                      pkt_idx[None, :] < cut1[:, None], True)
            deliv = deliv & part[:, None]
            if net.vote_deadline_s is not None:
                deliv = deliv & deadline_mask(arr1, net.vote_deadline_s)
            chunk_ok = deliv[:, pkt_of_chunk]
            counts = jnp.sum(votes_i32 * chunk_ok.astype(jnp.int32), axis=0)
            st1 = _masked_drain(jnp.where(deliv, arr1, jnp.inf), svc)
            t1 = jnp.where(st1.n_packets > 0, st1.completion_s,
                           jnp.max(jnp.where(part, train_s, -jnp.inf)))
            if net.vote_deadline_s is not None:
                t1 = jnp.maximum(t1, jnp.float32(net.vote_deadline_s))
            voter = chunk_ok.any(axis=1)
            up = (part & voter) if net.drop_late_voters else part
            up = up & ~crash_p1
            n_part = jnp.sum(part.astype(jnp.int32))
            return {
                "part": part, "strag": strag, "eff_rates": eff_rates,
                "counts": counts, "t1": t1, "up": up,
                "crash_p2": crash_p2, "cut2": u_cut[:, 1],
                "crashed": jnp.sum((crashed & part).astype(jnp.int32)),
                "n_part": n_part,
                "n_up": jnp.sum(up.astype(jnp.int32)),
                "votes_lost": n_part * p1_pkts
                              - jnp.sum(deliv.astype(jnp.int32)),
                "delivered_chunks": jnp.sum(chunk_ok.astype(jnp.int32)),
            }

        svc = jnp.float32(dyn["svc"])
        base_keys = (k_part, k_strag, k_arr1, k_loss1,
                     jax.random.fold_in(rk, _KEY_GE),
                     jax.random.fold_in(rk, _KEY_CRASH))
        if not quorum:
            r = phase1_attempt(base_keys)
            aborted = jnp.zeros((), bool)
            attempts = jnp.int32(1)
            penalty = None
            n_part_total = r["n_part"]
        else:
            # quorum-or-abort: re-run the network phase (fresh draws from
            # the retry keys, same votes) until >= quorum_floor uploaders
            # survive, spending each failed attempt's phase-1 time plus an
            # exponential backoff on the simulated clock.
            results = [phase1_attempt(base_keys)]
            for i in range(1, n_attempts):
                ki = jax.random.fold_in(rk, _KEY_RETRY + i)
                results.append(phase1_attempt(
                    tuple(jax.random.split(ki, 6))))
            stacked = {k: jnp.stack([r[k] for r in results])
                       for k in results[0]}
            ok = stacked["n_up"] >= jnp.int32(net.quorum_floor)
            ok_any = jnp.any(ok)
            sel = jnp.where(ok_any, jnp.argmax(ok).astype(jnp.int32),
                            jnp.int32(n_attempts - 1))
            aborted = ~ok_any
            attempts = sel + 1
            idx = jnp.arange(n_attempts, dtype=jnp.int32)
            backoff = net.retry_policy().delays(n_attempts,
                                                base=dyn["backoff_s"])
            penalty = jnp.sum(jnp.where(idx < sel,
                                        stacked["t1"] + backoff, 0.0))
            n_part_total = jnp.sum(jnp.where(idx <= sel,
                                             stacked["n_part"], 0))
            r = {k: jnp.take(v, sel, axis=0) for k, v in stacked.items()}

        part, strag, up = r["part"], r["strag"], r["up"]
        counts, t1, eff_rates = r["counts"], r["t1"], r["eff_rates"]
        crash_p2, n_up = r["crash_p2"], r["n_up"]
        t_gia = download_time(gia_pkts, rates)

        # ---- GIA + phase-2 compress: identical to the plain core.  The
        # scale f and threshold a are derived from the *announced* uploader
        # set (the GIA broadcast precedes phase-2 crashes).
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

        # ---- phase 2 through the register bank, with faults.
        start2 = t1 + t_gia if penalty is None else t1 + t_gia + penalty
        st2, n_retx, retx_last, n_win, dup_slot, n_dup, n_reset = \
            _chaos_upload(
                k_arr2, k_retx, jax.random.fold_in(rk, _KEY_DUP),
                jax.random.fold_in(rk, _KEY_JITTER),
                jax.random.fold_in(rk, _KEY_RESET),
                eff_rates, start2, q_bufs.shape[1], tr.phase2_bytes,
                leaf_of, svc, loss=dyn["loss"], rto_s=net.rto_s,
                max_retries=net.max_retries, memory_slots=net.memory_slots,
                n_leaves=net.n_leaves, mtu=net.mtu, not_before=start2,
                up=up, crash_p2=crash_p2, cut_frac=r["cut2"],
                dup_rate=dyn["dup_rate"], jitter_s=dyn["reorder_jitter_s"],
                reset_rate=dyn["reg_reset_rate"])

        # ---- commit: all-or-nothing per client.  A phase-2 crasher's
        # partial upload commits none of its slots; an aborted round
        # commits nobody (delta 0, residuals fall back to u).
        committed = up & ~crash_p2
        if quorum:
            committed = committed & ~aborted
        n_commit = jnp.sum(committed.astype(jnp.int32))
        rows = jnp.where(committed[:, None], q_bufs, 0)
        if not net.dedup:
            # no duplicate suppression: every duplicated packet's slots
            # deposit a second time (the double-count the sequence-number
            # policy exists to prevent).
            rows = rows + jnp.where(committed[:, None] & dup_slot, q_bufs, 0)
        c_live = q_bufs.shape[1]
        summed, reg_ovf, reg_shift = register_accumulate(
            rows, policy=net.register_policy,
            slot_window=slot_window(c_live, net.memory_slots),
            n_windows=n_win)
        if net.register_policy == "rescale":
            # Overflowed windows come back as mantissa x 2^shift; apply the
            # exponent in float during decompression (a sum past the int32
            # rails has no integer representation).  shift == 0 everywhere
            # on a clean round, and int32 -> f32 happens at the same point
            # the plain path's .astype(jnp.float32) does, so the fault-free
            # aggregate stays bit-identical.
            summed = summed.astype(jnp.float32) * jnp.exp2(
                reg_shift.astype(jnp.float32))
        n_commit_safe = jnp.maximum(n_commit, 1)
        if cfg.compact_mode == "block":
            delta = compaction.block_scatter(
                summed, plan.keep_dense, plan.pos, d, cfg.block_size,
                cfg.capacity_frac).astype(jnp.float32) / (n_commit_safe * f)
        else:
            delta = scatter_sum(summed, plan.idx, plan.keep, cfg,
                                d).astype(jnp.float32) / (n_commit_safe * f)
        delta = jnp.where(n_commit > 0, delta, 0.0)
        residuals = jnp.where(committed[:, None], res, u_stack)

        t2 = jnp.maximum(st2.completion_s, start2)
        wall2 = t2 + download_time(n_packets(tr.phase2_bytes, net.mtu),
                                   rates)
        wall = jnp.where(n_commit > 0, wall2, start2)

        com_by_leaf = jax.ops.segment_sum(committed.astype(jnp.int32),
                                          jnp.asarray(leaf_of),
                                          num_segments=net.n_leaves)
        live_leaves = jnp.sum((com_by_leaf > 0).astype(jnp.int32))
        value_ops = jnp.sum(jnp.maximum(com_by_leaf - 1, 0)) * c_live
        if net.n_leaves > 1:
            value_ops = value_ops + jnp.maximum(live_leaves - 1, 0) * c_live
        aux = {
            "participants": part, "stragglers": strag, "uploaders": committed,
            "counts": counts,
            "n_part": n_part_total, "n_up": n_commit,
            "n_strag": jnp.sum(strag.astype(jnp.int32)),
            "votes_lost": r["votes_lost"],
            "retransmissions": n_retx, "retx_last": retx_last,
            "wall_clock_s": wall, "phase1_s": t1,
            "phase2_s": t2 - t1,
            "mean_wait_s": st2.mean_wait_s,
            "aggregation_ops": r["delivered_chunks"]
                               + jnp.where(n_commit > 0, value_ops, 0),
            "peak_live_slots": jnp.where(n_commit > 0,
                                         min(net.memory_slots, c_live), 0),
            "passes": jnp.int32(n_win),
            # chaos extras (stats only — never enter FLHistory)
            "crashed": r["crashed"],
            "duplicates": n_dup, "resets": n_reset,
            "overflow_slots": jnp.sum(reg_ovf.astype(jnp.int32)),
            "aborted": aborted.astype(jnp.int32),
            "attempts": attempts,
        }
        return delta, residuals, aux

    return core


def chaos_packet_dyn(cfg: FediACConfig, net: FaultConfig, n_clients: int,
                     local_train_s: float, svc: float) -> dict:
    """The traced ``dyn`` dict of one chaos scenario: the benign
    :func:`~repro.netsim.batched.packet_dyn` scalars plus the fault
    rates, in :data:`FAULT_DYN_FIELDS` order."""
    dyn = packet_dyn(cfg, net, n_clients, local_train_s, svc)
    dyn.update({
        "ge_p_gb": jnp.float32(net.ge_p_gb),
        "ge_p_bg": jnp.float32(net.ge_p_bg),
        "ge_loss_bad": jnp.float32(net.ge_loss_bad),
        "crash_rate": jnp.float32(net.crash_rate),
        "crash_p2_frac": jnp.float32(net.crash_p2_frac),
        "dup_rate": jnp.float32(net.dup_rate),
        "reorder_jitter_s": jnp.float32(net.reorder_jitter_s),
        "reg_reset_rate": jnp.float32(net.reg_reset_rate),
        "backoff_s": jnp.float32(net.backoff_s),
    })
    return dyn
