"""The jittable fixed-shape packet round core (DESIGN.md §13).

``make_fediac_packet_core`` builds a *pure-JAX* function of one FediAC
packet round — participant sampling, stragglers, Poisson vote packets with
loss and the quorum deadline, the GIA, phase-2 compression, ARQ'd value
packets through the register-window / leaf->root hierarchy drains, and the
simulated wall-clock — with every data-dependent quantity expressed as a
masked fixed-``[N]`` formulation:

* participant/straggler/uploader selection returns boolean masks, never
  ``np.flatnonzero`` index arrays; absent clients' packets are ``+inf``
  arrivals (``timeline``'s masking convention) and their value rows are
  ``where``-masked out of the integer aggregate;
* the ``n_up == 0`` round is the same program under a ``where``: the
  uploader mask is all-False, so the residual stack falls back to ``u``
  and the delta to zeros exactly — zero-uploader rounds stay bit-exact;
* quantities the host resolves from the data-dependent uploader count
  (the vote threshold ``a = cfg.threshold(n_up)`` and the quantization
  numerator ``2^{b-1} - n_up``) are precomputed host-side as ``[N+1]``
  lookup tables and gathered at the traced ``n_up`` — bit-identical to
  the host float64 arithmetic, no f32 re-derivation drift.

All network randomness derives from ``policies.net_round_key(seed,
round_idx)``; the model randomness (votes, stochastic quantization) uses
the FL loop's round key exactly as ``aggregate_stack`` does, so the
lossless full-participation round remains bit-identical to the in-memory
engine.  The per-cell scenario knobs — loss, participation, straggler
fraction, local train time, switch service time, and the threshold table —
enter through the ``dyn`` dict as traced scalars, which is what lets the
sweep fleet stack same-shape packet scenarios into one ``jit(vmap)`` round
program (``sweep/fleet.py``).

``reliable_upload`` (phase-2 scheduling: packet->window map, Poisson
arrivals, ARQ delays, hierarchical drain) is shared with the baseline
packet path in ``transport.py`` — it accepts either concrete subset rows
(the eager baseline path) or full masked rows (this core).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.fediac import (FediACConfig, build_round_plan,
                               client_vote_stack, phase2_compress,
                               plan_wants_dense_mask, round_traffic,
                               scatter_sum)
from repro.core import compaction, engines
from repro.core.shard_engine import shard_compress_stack
from repro.core.stream_engine import stream_compress_stack
from repro.switch import n_packets, packet_sizes

from .dataplane import n_windows
from .hierarchy import drain_hierarchy, leaf_assignment
from .policies import NetConfig, sample_participants, sample_stragglers
from .timeline import (_masked_drain, deadline_mask, download_time,
                       lose_packets, poisson_arrivals, retransmit_delays)

__all__ = ["threshold_table", "scale_num_table", "reliable_upload",
           "retx_byte_count", "make_fediac_packet_core", "packet_dyn",
           "PACKET_DYN_FIELDS"]

# the traced per-cell scalars of one packet scenario, in the order the
# fleet stacks them (DESIGN.md §13)
PACKET_DYN_FIELDS = ("a_table", "loss", "participation", "straggler_frac",
                     "local_train_s", "svc")


def threshold_table(cfg: FediACConfig, n_clients: int) -> np.ndarray:
    """int32[N+1] — ``cfg.threshold(m)`` for every possible uploader count
    m.  The host resolves the vote threshold from the *data-dependent*
    quorum size; the traced core gathers this host-float64-exact table at
    the traced ``n_up`` instead of re-deriving ``ceil(a_frac * n_up)`` in
    f32 (whose rounding can differ from ``math.ceil``)."""
    return np.array([1] + [cfg.threshold(m) for m in range(1, n_clients + 1)],
                    np.int32)


def scale_num_table(bits: int, n_clients: int) -> np.ndarray:
    """float32[N+1] — the quantization numerator ``2^{b-1} - m`` over ``m``
    for every uploader count, computed in float64 then cast, matching
    ``scale_factor(bits, m, 1.0)`` casts on the host path bit-for-bit."""
    return np.array([1.0] + [(2.0 ** (bits - 1) - m) / m
                             for m in range(1, n_clients + 1)], np.float32)


def reliable_upload(k_arr, k_retx, rates_rows, start, live_slots: int,
                    wire_bytes: int, leaf_of: np.ndarray, svc, *,
                    loss, rto_s, max_retries: int, memory_slots: int,
                    n_leaves: int, mtu: int, not_before=0.0, row_mask=None):
    """Schedule one reliable upload through the register windows — packet->
    window map, Poisson arrivals, ARQ delays, hierarchical drain — shared
    by the traced FediAC phase 2 (full rows + ``row_mask``) and the eager
    baseline path (pre-filtered subset rows, ``row_mask=None``).

    Returns ``(DrainStats, retransmission count, retransmissions of the
    final partial packet, window count)``; the counts are traced int32
    when the inputs are.  The retransmitted *bytes* are reconstructed
    host-side by :func:`retx_byte_count` — every packet but the last is
    MTU-sized, so two bounded int32 counts carry the exact figure without
    the int32 byte sum that would wrap at ~2.1 GB of retransmissions.
    """
    live = max(int(live_slots), 1)
    n_win = n_windows(live, memory_slots)
    pkts = n_packets(wire_bytes, mtu)
    slots_per_pkt = -(-live // pkts)
    pkt_window = np.minimum((np.arange(pkts) * slots_per_pkt)
                            // memory_slots, n_win - 1)
    arr = poisson_arrivals(k_arr, rates_rows, pkts, start)
    delay, retx = retransmit_delays(k_retx, arr.shape, loss, rto_s,
                                    max_retries)
    if row_mask is not None:
        arr = jnp.where(row_mask[:, None], arr, jnp.inf)
        retx = jnp.where(row_mask[:, None], retx, 0)
    fwd = n_packets(min(memory_slots, live) * 4, mtu)
    st = drain_hierarchy(arr + delay, leaf_assignment(arr.shape[0], n_leaves)
                         if leaf_of is None else leaf_of,
                         pkt_window, n_win, n_leaves, svc, fwd,
                         not_before=not_before)
    return st, jnp.sum(retx), jnp.sum(retx[:, -1]), n_win


def retx_byte_count(n_retx: int, retx_last: int, wire_bytes: int,
                    mtu: int) -> int:
    """Exact retransmitted bytes from the two traced counts (Python ints —
    arbitrary precision, no wraparound): full-size packets re-emit ``mtu``
    bytes, the final partial packet re-emits its own size."""
    last = int(packet_sizes(wire_bytes, mtu)[-1])
    return (int(n_retx) - int(retx_last)) * mtu + int(retx_last) * last


def make_fediac_packet_core(cfg: FediACConfig, net: NetConfig,
                            n_clients: int):
    """Build the traced FediAC packet round.

    Static structure comes from ``cfg`` (compression geometry, engine) and
    the structural ``net`` fields (deadline presence, quorum policy, ARQ
    constants, register bank, hierarchy depth, MTU); ``net.loss`` /
    ``net.participation`` / ``net.straggler_frac`` are IGNORED here — they
    ride per-call through ``dyn`` so one compiled program serves a whole
    loss x participation grid.  ``cfg.a`` / ``cfg.a_frac`` are likewise
    never read: the resolved per-``n_up`` threshold table arrives in
    ``dyn["a_table"]``.

    The returned ``core(u_stack, key, net_key, round_idx, rates, dyn)``
    is pure jax — ``jit`` it for the sequential transport, ``jit(vmap)``
    it for the fleet — and returns ``(delta, residuals, aux)`` where
    ``aux`` carries the masks, vote counts and traced accounting scalars
    the Python wrapper prices the round from.
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

    def core(u_stack, key, net_key, round_idx, rates, dyn):
        n_, d = u_stack.shape
        assert n_ == n, (n_, n)
        n_chunks = d // cfg.vote_chunk
        tr = round_traffic(cfg, d)
        p1_pkts = n_packets(tr.phase1_bytes, net.mtu)
        gia_pkts = n_packets(-(-n_chunks // 8), net.mtu)
        cov = -(-n_chunks // p1_pkts)      # chunk coords per vote packet
        pkt_of_chunk = np.minimum(np.arange(n_chunks) // cov, p1_pkts - 1)

        rk = jax.random.fold_in(net_key, round_idx)
        k_part, k_strag, k_arr1, k_loss1, k_arr2, k_retx = \
            jax.random.split(rk, 6)
        keys = jax.random.split(key, 2 * n)
        vote_keys, q_keys = keys[:n], keys[n:]

        # ---- round policies: masks, never index arrays.
        part = sample_participants(k_part, n, dyn["participation"])
        strag = sample_stragglers(k_strag, part, dyn["straggler_frac"])
        slow = jnp.where(strag, jnp.float32(slowdown), 1.0)
        train_s = jnp.float32(dyn["local_train_s"]) * slow
        eff_rates = jnp.asarray(rates, jnp.float32) / slow
        svc = jnp.float32(dyn["svc"])

        # ---- phase 1: vote packets (lossy, no ARQ — the quorum absorbs).
        # Votes are computed for all N rows (each from its own key, so each
        # row equals the full-stack computation) and masked by delivery.
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

        # ---- quorum: who goes on to phase 2.
        voter = chunk_ok.any(axis=1)
        up = (part & voter) if net.drop_late_voters else part
        n_up = jnp.sum(up.astype(jnp.int32))
        t_gia = download_time(gia_pkts, rates)

        # ---- GIA + phase-2 compress: the exact core.fediac machinery
        # against the packet-derived counts, run for every row and masked
        # by the uploader set (rows are key-independent of each other).
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
        summed = jnp.sum(jnp.where(up[:, None], q_bufs, 0), axis=0)
        n_up_safe = jnp.maximum(n_up, 1)
        if cfg.compact_mode == "block":
            delta = compaction.block_scatter(
                summed, plan.keep_dense, plan.pos, d, cfg.block_size,
                cfg.capacity_frac).astype(jnp.float32) / (n_up_safe * f)
        else:
            delta = scatter_sum(summed, plan.idx, plan.keep, cfg,
                                d).astype(jnp.float32) / (n_up_safe * f)
        delta = jnp.where(n_up > 0, delta, 0.0)
        residuals = jnp.where(up[:, None], res, u_stack)

        # ---- phase 2: reliable int32 packets through the register bank.
        st2, n_retx, retx_last, n_win = reliable_upload(
            k_arr2, k_retx, eff_rates, t1 + t_gia, q_bufs.shape[1],
            tr.phase2_bytes, leaf_of, svc, loss=dyn["loss"],
            rto_s=net.rto_s, max_retries=net.max_retries,
            memory_slots=net.memory_slots, n_leaves=net.n_leaves,
            mtu=net.mtu, not_before=t1 + t_gia, row_mask=up)
        # with zero uploaders every phase-2 packet is masked and the
        # multi-leaf drain completes at -inf; clamp the phase-2 clock to
        # its start so the stats stay finite (wall falls back below)
        t2 = jnp.maximum(st2.completion_s, t1 + t_gia)
        wall2 = t2 + download_time(n_packets(tr.phase2_bytes, net.mtu),
                                   rates)
        wall = jnp.where(n_up > 0, wall2, t1 + t_gia)

        # ---- value-plane accounting the register-bank walk would report
        # (psim semantics priced analytically from the masks; the sums are
        # associative so the values never depend on the walk itself).
        c_live = q_bufs.shape[1]
        up_by_leaf = jax.ops.segment_sum(up.astype(jnp.int32),
                                         jnp.asarray(leaf_of),
                                         num_segments=net.n_leaves)
        live_leaves = jnp.sum((up_by_leaf > 0).astype(jnp.int32))
        value_ops = jnp.sum(jnp.maximum(up_by_leaf - 1, 0)) * c_live
        if net.n_leaves > 1:
            value_ops = value_ops + jnp.maximum(live_leaves - 1, 0) * c_live
        n_part = jnp.sum(part.astype(jnp.int32))
        delivered_chunks = jnp.sum(chunk_ok.astype(jnp.int32))
        aux = {
            "participants": part, "stragglers": strag, "uploaders": up,
            "counts": counts,
            "n_part": n_part, "n_up": n_up,
            "n_strag": jnp.sum(strag.astype(jnp.int32)),
            "votes_lost": n_part * p1_pkts
                          - jnp.sum(deliv.astype(jnp.int32)),
            "retransmissions": n_retx, "retx_last": retx_last,
            "wall_clock_s": wall, "phase1_s": t1,
            "phase2_s": t2 - t1,
            "mean_wait_s": st2.mean_wait_s,
            "aggregation_ops": delivered_chunks + jnp.where(n_up > 0,
                                                            value_ops, 0),
            "peak_live_slots": jnp.where(n_up > 0,
                                         min(net.memory_slots, c_live), 0),
            "passes": jnp.int32(n_win),
        }
        return delta, residuals, aux

    return core


def packet_dyn(cfg: FediACConfig, net: NetConfig, n_clients: int,
               local_train_s: float, svc: float) -> dict:
    """The ``dyn`` dict for one scenario, as weak host scalars + the
    threshold table — build once, pass to every round (the fleet stacks
    one of these per cell)."""
    return {"a_table": jnp.asarray(threshold_table(cfg, n_clients)),
            "loss": jnp.float32(net.loss),
            "participation": jnp.float32(net.participation),
            "straggler_frac": jnp.float32(net.straggler_frac),
            "local_train_s": jnp.float32(local_train_s),
            "svc": jnp.float32(svc)}
