"""The sharded multi-device round engine: FediAC over the coordinate axis.

``aggregate_stack`` and ``aggregate_stream`` both run the round on one
device; this module runs it over the ``d`` (coordinate) axis of a device
mesh (DESIGN.md §16), so per-device peak memory falls ~1/devices — the
step that takes the reproduction toward the billion-parameter regime the
ROADMAP names.  Every device owns a contiguous coordinate shard and the
round is reassembled from four small collectives:

1. **Phase-1 votes are shard-local.**  Threshold voting needs only each
   client's global max |u| (one ``pmax`` of per-shard maxes).  Gumbel
   top-k voting needs each client's global k-th score: per-shard scores
   are reconstructed bit-exactly from the counter-based stream slices
   (:func:`repro.core.streams.gumbel_block`), mapped to order-preserving
   uint32 keys, and the k-th largest key is found by a 32-pass MSB-first
   bisection whose only communication is a ``psum``'d count per pass.
   Boundary ties resolve by global coordinate index (shard tie counts are
   all-gathered once), which is exactly the stable ``lax.top_k``
   tie-break ``selection.topk_mask`` certifies.

2. **The consensus threshold comes from per-shard count histograms.**
   Vote counts are small ints (≤ N), so one ``psum``'d ``[N+1]``
   histogram determines the C-th largest count c* and the tie budget
   ``C - n_gt`` — the same values ``selection.consensus_topk`` bisects
   for — without any d-sized sort or gather.

3. **Compact-buffer slots come from one all-gather of per-shard slot
   counts.**  A selected coordinate's buffer slot is ``#(count > c) +
   rank among count == c by global index``; the first term is a suffix of
   the global histogram and the second needs only each shard's per-class
   counts (the all-gather) plus a local stable sort.  Each device then
   reads its own coordinates' quantization uniforms in place with
   :func:`repro.core.streams.uniform_at` — the C-sized uniform stream is
   never materialized.

4. **Phase-2 gather/scatter/residual updates stay shard-local**, using
   the per-coordinate cast chains of the streaming engine (which are
   pinned bit-identical to ``client_compress``/``scatter_compact``), so
   the sharded round is bit-identical to ``aggregate_stack`` for every
   vote × compact mode (``tests/test_shard_engine.py`` /
   ``tests/test_engine_matrix.py``).

Coordinates are zero-padded up to a multiple of (devices × block granule).
Padding is inert by construction: pad coordinates carry count 0, sort
after every true coordinate (they occupy the largest global indices), and
are masked out of histograms, votes, and outputs.

``vote_chunk > 1`` and the fused Pallas kernels are not sharded; those
modes keep the monolithic engine (same policy as the streaming engine).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.compat import make_mesh
from repro.obs.scopes import scope

from . import compaction, robust_agg, voting
from .quantize import dequantize, quantize, scale_factor
from .round_plan import RoundPlan
from .streams import gumbel_block, uniform_at

__all__ = ["aggregate_shard", "shard_compress_stack", "shard_geometry",
           "shard_mesh"]


def _check_shardable(cfg):
    if cfg.vote_chunk != 1:
        raise NotImplementedError(
            "the sharded engine requires vote_chunk == 1 "
            "(chunked vote bits keep the monolithic engine)")
    if getattr(cfg, "use_pallas", False):
        raise NotImplementedError(
            "the sharded engine does not route through the fused Pallas "
            "kernels; use the monolithic or stream engine for use_pallas")


def shard_geometry(d: int, n_dev: int, cfg) -> tuple[int, int]:
    """(shard size S, padded length D = S * n_dev) for a d-vector.

    In block-compact mode S is rounded up to a ``block_size`` multiple so
    blocks never straddle shards (the same locality invariant the stream
    engine keeps for chunks).
    """
    s = -(-d // n_dev)
    if cfg.compact_mode == "block":
        bs = int(cfg.block_size)
        s = -(-s // bs) * bs
    return s, s * n_dev


def shard_mesh(devices: int | None = None, axis: str = "d"):
    """A 1-D coordinate mesh over ``devices`` (default: all visible).

    The engine builds its mesh here, so a caller that places ``u_stack``
    with ``NamedSharding(shard_mesh(...), P(None, axis))`` hands it over
    already sharded: the stack never lands whole on one device."""
    n_dev = int(devices) if devices else len(jax.devices())
    return make_mesh((n_dev,), (axis,))


def _pad_cols(x: jax.Array, width: int) -> jax.Array:
    pad = width - x.shape[-1]
    if pad == 0:
        return x
    cfgpad = [(0, 0)] * (x.ndim - 1) + [(0, pad)]
    return jnp.pad(x, cfgpad)


def _f32_sort_keys(x: jax.Array) -> jax.Array:
    """Order-preserving uint32 keys of float32 (no NaN): flip all bits of
    negatives, set the sign bit of non-negatives.  -inf maps to the global
    minimum key, so padded -inf scores never outrank a finite score.
    FediAC vote scores contain no -0.0 (``log|clip(u)| + gumbel`` sums of
    nonzero finites round exact cancellation to +0.0), so key equality
    coincides with float equality and the tie class is unambiguous."""
    b = jax.lax.bitcast_convert_type(x, jnp.uint32)
    neg = (b >> np.uint32(31)).astype(bool)
    return jnp.where(neg, ~b, b | np.uint32(0x80000000))


def _kth_largest_key(keys_u: jax.Array, k: int, axis: str) -> jax.Array:
    """Per-row k-th largest uint32 key over the global (all-shard)
    coordinate axis: MSB-first bisection, one psum'd count per bit."""
    rows = keys_u.shape[0]

    def bit(i, acc):
        cand = acc | (jnp.uint32(1) << jnp.asarray(31 - i, jnp.uint32))
        ge = jax.lax.psum(
            jnp.sum((keys_u >= cand[:, None]).astype(jnp.int32), axis=1),
            axis)
        return jnp.where(ge >= k, cand, acc)

    return jax.lax.fori_loop(0, 32, bit, jnp.zeros((rows,), jnp.uint32))


def _shard_offsets(per_shard: jax.Array, me, axis: str) -> jax.Array:
    """Exclusive prefix over the mesh axis of per-shard counts: one
    all-gather, then a masked sum of the shards before ``me``."""
    allc = jax.lax.all_gather(per_shard, axis)          # [n_dev, ...]
    n_dev = allc.shape[0]
    before = jnp.arange(n_dev, dtype=jnp.int32) < me
    shape = (n_dev,) + (1,) * (allc.ndim - 1)
    return jnp.sum(jnp.where(before.reshape(shape), allc, 0), axis=0)


def _suffix_counts(hist: jax.Array) -> jax.Array:
    """[n+2] suffix sums G of an [n+1] count histogram: G[v] = #counts >= v
    (G[n+1] = 0)."""
    g = jnp.cumsum(hist[::-1])[::-1].astype(jnp.int32)
    return jnp.concatenate([g, jnp.zeros((1,), jnp.int32)])


def _phase1_counts(u_loc, cfg, vote_keys, k: int, d: int, start, valid,
                   axis: str):
    """Shard-local phase 1: (counts_loc int32[S], m scalar f32 global max).

    Threshold mode needs one pmax of per-client maxes; Gumbel top-k mode
    reconstructs this shard's score slice from the counter-based stream
    and resolves the per-client global top-k by key bisection + index-
    ordered tie fill — the stable ``lax.top_k`` set, member for member.
    """
    n, s = u_loc.shape
    if cfg.vote_mode == "threshold":
        m_vec = jax.lax.pmax(jnp.max(jnp.abs(u_loc), axis=1), axis)
        tau = voting.vote_tau(m_vec, k, cfg.alpha)

        def cnt(uc, vc):
            return ((jnp.abs(uc) >= tau[:, None]) & vc[None, :]
                    ).astype(jnp.int32).sum(axis=0)

        cs = min(s, _PHASE2_CHUNK)
        if s <= cs:
            return cnt(u_loc, valid), jnp.max(m_vec)

        # Wide shards stream the vote count in column chunks so |u| is
        # never materialized at [N, S]; the clamped tail overlap rewrites
        # identical per-coordinate counts (same rule as _phase2_chunked).
        def step(i, counts):
            st = jnp.minimum(i * cs, s - cs)
            cc = cnt(jax.lax.dynamic_slice(u_loc, (jnp.int32(0), st),
                                           (n, cs)),
                     jax.lax.dynamic_slice(valid, (st,), (cs,)))
            return jax.lax.dynamic_update_slice(counts, cc, (st,))

        counts = jax.lax.fori_loop(0, -(-s // cs), step,
                                   jnp.zeros((s,), jnp.int32))
        return counts, jnp.max(m_vec)
    logw = jnp.log(jnp.clip(jnp.abs(u_loc).astype(jnp.float32), 1e-30,
                            None))
    g = jax.vmap(lambda kk: gumbel_block(kk, start, s, d))(vote_keys)
    scores = jnp.where(valid[None, :], logw + g, -jnp.inf)
    keys_u = _f32_sort_keys(scores)
    t_key = _kth_largest_key(keys_u, k, axis)
    gt = keys_u > t_key[:, None]
    eq = keys_u == t_key[:, None]
    n_gt = jax.lax.psum(gt.astype(jnp.int32).sum(axis=1), axis)
    tie_off = _shard_offsets(eq.astype(jnp.int32).sum(axis=1),
                             jax.lax.axis_index(axis), axis)
    lrank = jnp.cumsum(eq.astype(jnp.int32), axis=1) - eq
    take = eq & ((tie_off[:, None] + lrank) < (k - n_gt)[:, None])
    mask = gt | take
    m = jax.lax.pmax(jnp.max(jnp.abs(u_loc)), axis)
    return mask.astype(jnp.int32).sum(axis=0), m


def _consensus_shards(counts_loc, valid, n: int, capacity: int, me,
                      axis: str):
    """Shard-local consensus selection from the global count histogram:
    (sel bool[S], slot int32[S], Garr int32[n+2] global suffix counts).

    ``sel`` is the stable top-C membership (count-desc, index-asc ties —
    ``selection.consensus_topk``'s exact rule); ``slot`` is each selected
    coordinate's position in that order, i.e. its compact-buffer slot.
    """
    s = counts_loc.shape[0]
    valid_i = valid.astype(jnp.int32)
    hist_loc = jnp.zeros((n + 1,), jnp.int32).at[counts_loc].add(valid_i)
    garr = _suffix_counts(jax.lax.psum(hist_loc, axis))
    vs = jnp.arange(n + 1, dtype=jnp.int32)
    c_star = jnp.max(jnp.where(garr[:-1] >= capacity, vs, 0))
    n_gt = jnp.take(garr, c_star + 1)
    # rank within this shard's count class: one stable sort (count desc,
    # index asc), inverted to rank_of, minus the local #(count > class).
    iota = jnp.arange(s, dtype=jnp.int32)
    _, order = jax.lax.sort((-counts_loc, iota), num_keys=1, is_stable=True)
    rank_of = jnp.zeros((s,), jnp.int32).at[order].set(iota)
    lsuf = _suffix_counts(hist_loc)
    lrank = rank_of - jnp.take(lsuf, counts_loc + 1)
    cls_off = _shard_offsets(hist_loc, me, axis)        # [n+1]
    grank = jnp.take(cls_off, counts_loc) + lrank
    sel = ((counts_loc > c_star)
           | ((counts_loc == c_star) & (grank < capacity - n_gt))) & valid
    slot = jnp.take(garr, counts_loc + 1) + grank
    return sel, slot, garr


def _floored_threshold(cfg, a_arr, garr, n: int):
    """``round_plan.consensus_floor_threshold`` from the histogram: the
    live-coordinate count is the suffix sum at ``a``."""
    if getattr(cfg, "consensus_floor", 0) <= 0:
        return a_arr
    live = jnp.take(garr, jnp.clip(a_arr, 0, n + 1))
    return jnp.where(live < jnp.int32(cfg.consensus_floor), jnp.int32(1),
                     a_arr)


def _topk_coord_phase2(u_loc, cfg, f, q_keys, keep_f, slot, capacity: int):
    """Per-coordinate topk-compact phase 2 on one shard — the streaming
    engine's ``_topk_chunk`` cast chain (pinned bit-identical to
    ``client_compress``), with the slot's uniform read in place via
    ``uniform_at`` instead of gathered from a C-sized draw.  ``q`` is zero
    at every non-kept coordinate, so unselected coordinates (clipped
    dummy slots) contribute exact zeros everywhere."""
    dt = u_loc.dtype
    slot_c = jnp.clip(slot, 0, capacity - 1)
    uni = jax.vmap(lambda kk: uniform_at(kk, slot_c, capacity))(q_keys)
    gathered = ((u_loc.astype(jnp.float32) * keep_f[None, :]).astype(dt)
                ).astype(jnp.float32)
    q = quantize(gathered, f, uni)
    up = dequantize(q, f).astype(dt)
    vals = (up.astype(jnp.float32) * keep_f[None, :]).astype(dt)
    return q, u_loc - vals


def _block_coord_phase2(u_loc, cfg, f, q_keys, keep_b, gidx, d: int):
    """Per-coordinate block-compact phase 2 on one shard — the streaming
    engine's ``_phase2_block`` math against the coordinate's slice of the
    per-client d-sized uniform stream."""
    dt = u_loc.dtype
    gclip = jnp.clip(gidx, 0, d - 1)
    uni = jax.vmap(lambda kk: uniform_at(kk, gclip, d))(q_keys)
    q = quantize(jnp.where(keep_b[None, :], u_loc, 0.0), f, uni)
    res = (u_loc - jnp.where(keep_b[None, :], dequantize(q, f), 0.0)
           ).astype(dt)
    return q, res


# Per-device phase-2 column chunk: above this shard width the [N, S]
# uniform/quantize/dequantize temporaries are streamed through an inner
# fori_loop instead of materialized, bounding per-device temp memory by
# the chunk (the within-shard analogue of the streaming engine's scan).
_PHASE2_CHUNK = 1 << 18


def _phase2_chunked(u_loc, fn, cs: int):
    """Run phase 2 over ``cs``-wide column chunks of one shard, writing
    ``delta``/``res`` in place (the loop carry aliases, so XLA updates the
    output buffers without a second [N, S] copy).

    The final chunk's start is clamped to ``S - cs`` so it re-reads the
    tail: every phase-2 value is a pure function of its coordinate, so the
    overlapped writes are idempotent and bit-identity is preserved.
    ``fn(u_chunk, start) -> (delta_chunk [cs], res_chunk [N, cs])``.
    """
    n, s = u_loc.shape
    nc = -(-s // cs)

    def step(i, acc):
        delta, res = acc
        start = jnp.minimum(i * cs, s - cs)
        dc, rc = fn(jax.lax.dynamic_slice(u_loc, (jnp.int32(0), start),
                                          (n, cs)), start)
        return (jax.lax.dynamic_update_slice(delta, dc, (start,)),
                jax.lax.dynamic_update_slice(res, rc, (jnp.int32(0), start)))

    return jax.lax.fori_loop(
        0, nc, step, (jnp.zeros((s,), jnp.float32),
                      jnp.zeros((n, s), u_loc.dtype)))


def aggregate_shard(u_stack: jax.Array, cfg, key: jax.Array, *, a=None,
                    devices: int | None = None, axis: str = "d"):
    """One FediAC round sharded over the coordinate axis — bit-identical
    to :func:`repro.core.fediac.aggregate_stack` (same signature and
    ``(delta, residuals, counts, TrafficStats)`` contract) with per-device
    peak memory ~1/devices of the monolithic round.

    ``devices`` sizes the 1-D mesh (default: every visible device); ``a``
    optionally overrides the vote threshold and may be traced, exactly as
    in the other engines.  Composes under ``jit`` and under the fleet
    ``vmap`` (the mesh is built at trace time).
    """
    from .fediac import round_traffic  # local import: fediac imports us

    n, d = u_stack.shape
    _check_shardable(cfg)
    mesh = shard_mesh(devices, axis)
    s, width = shard_geometry(d, mesh.size, cfg)
    with scope("vote"):
        keys = jax.random.split(key, 2 * n)
        vote_keys, q_keys = keys[:n], keys[n:]
    k = min(cfg.k(d), d)
    capacity = cfg.capacity(d)
    a_arr = jnp.asarray(cfg.threshold(n) if a is None else a, jnp.int32)

    def body(u_loc, vks, qks, a_in):
        me = jax.lax.axis_index(axis)
        start = me * s
        gidx = start + jnp.arange(s, dtype=jnp.int32)
        valid = gidx < d
        with scope("vote"):
            counts_loc, m = _phase1_counts(u_loc, cfg, vks, k, d, start,
                                           valid, axis)
        with scope("consensus"):
            f = scale_factor(cfg.bits, n, 1.0) / jnp.clip(m, 1e-12, None)
            if cfg.compact_mode == "block":
                if getattr(cfg, "consensus_floor", 0) > 0:
                    hist_loc = jnp.zeros((n + 1,), jnp.int32).at[
                        counts_loc].add(valid.astype(jnp.int32))
                    garr = _suffix_counts(jax.lax.psum(hist_loc, axis))
                else:
                    garr = None
                a_eff = _floored_threshold(cfg, a_in, garr, n)
                keep_b, _ = compaction.block_select(counts_loc, a_eff,
                                                    cfg.block_size,
                                                    cfg.capacity_frac)
                keep_b = keep_b & valid
            else:
                sel, slot, garr = _consensus_shards(counts_loc, valid, n,
                                                    capacity, me, axis)
                a_eff = _floored_threshold(cfg, a_in, garr, n)
                keep_f = (sel & (counts_loc >= a_eff)).astype(jnp.float32)

        if cfg.compact_mode == "block":
            bs = int(cfg.block_size)
            cs = min(s, -(-_PHASE2_CHUNK // bs) * bs)

            def p2(uc, st):
                kc = jax.lax.dynamic_slice(keep_b, (st,), (cs,))
                gc = start + st + jnp.arange(cs, dtype=jnp.int32)
                q, rc = _block_coord_phase2(uc, cfg, f, qks, kc, gc, d)
                qagg, kept = robust_agg.client_sum(q, cfg)
                dc = jnp.where(kc, qagg,
                               0).astype(jnp.float32) / (kept * f)
                return dc, rc

        else:
            cs = min(s, _PHASE2_CHUNK)

            def p2(uc, st):
                kc = jax.lax.dynamic_slice(keep_f, (st,), (cs,))
                sc = jax.lax.dynamic_slice(slot, (st,), (cs,))
                q, rc = _topk_coord_phase2(uc, cfg, f, qks, kc, sc, capacity)
                # scatter_compact's exact cast chain, coordinate-wise
                qagg, kept = robust_agg.client_sum(q, cfg)
                dc = ((qagg.astype(jnp.float32) * kc)
                      .astype(jnp.int32)).astype(jnp.float32) / (kept * f)
                return dc, rc

        with scope("phase2"):
            if s <= cs:
                delta_loc, res = p2(u_loc, jnp.int32(0))
            else:
                delta_loc, res = _phase2_chunked(u_loc, p2, cs)
        return delta_loc, res, counts_loc

    run = jax.shard_map(body, mesh=mesh,
                        in_specs=(P(None, axis), P(), P(), P()),
                        out_specs=(P(axis), P(None, axis), P(axis)),
                        check_vma=False)
    delta, residuals, counts = run(_pad_cols(u_stack, width), vote_keys,
                                   q_keys, a_arr)
    return (delta[:d], residuals[:, :d], counts[:d], round_traffic(cfg, d))


def shard_compress_stack(u_stack: jax.Array, cfg, f, q_keys: jax.Array,
                         plan: RoundPlan, *, devices: int | None = None,
                         axis: str = "d"):
    """Coordinate-sharded phase 2 returning per-client compact buffers:
    ``(q_bufs [N, C], residuals [N, d])``, bit-identical to
    ``vmap(phase2_compress(cfg))`` against the same (global) plan — the
    packet-dataplane entry, mirroring ``stream_compress_stack``.

    Residuals stay shard-local; the wire buffers are assembled by one
    psum of shard-local scatter-adds (topk: each shard owns disjoint
    slots) or by shard-contiguous concatenation (block: blocks never
    straddle shards).  For topk mode ``plan`` must carry the dense mask
    and slot map (``build_round_plan(..., with_dense_mask=True,
    with_slot_map=True)``).
    """
    n, d = u_stack.shape
    _check_shardable(cfg)
    mesh = shard_mesh(devices, axis)
    s, width = shard_geometry(d, mesh.size, cfg)
    u_pad = _pad_cols(u_stack, width)

    if cfg.compact_mode == "block":
        nb, cb, _ = compaction.block_plan(d, cfg.block_size,
                                          cfg.capacity_frac)

        def body(u_loc, qks, keep_c, pos_c):
            me = jax.lax.axis_index(axis)
            gidx = me * s + jnp.arange(s, dtype=jnp.int32)
            q, res = _block_coord_phase2(u_loc, cfg, f, qks, keep_c, gidx, d)
            qb = jax.vmap(lambda qq: compaction.block_compact(
                qq, keep_c, pos_c, cfg.block_size, cfg.capacity_frac))(q)
            return qb, res

        run = jax.shard_map(body, mesh=mesh,
                            in_specs=(P(None, axis), P(), P(axis), P(axis)),
                            out_specs=(P(None, axis), P(None, axis)),
                            check_vma=False)
        q_bufs, residuals = run(u_pad, q_keys,
                                _pad_cols(plan.keep_dense, width),
                                _pad_cols(plan.pos, width))
        return q_bufs[:, :nb * cb], residuals[:, :d]

    capacity = plan.idx.shape[0]

    def body(u_loc, qks, sel_c, slot_c):
        keep_f = sel_c.astype(jnp.float32)
        q, res = _topk_coord_phase2(u_loc, cfg, f, qks, keep_f, slot_c,
                                    capacity)
        # q is 0 at every non-kept coordinate, so the dummy slot-0 adds
        # from masked coordinates are exact no-ops (stream engine rule).
        qb = jnp.zeros((n, capacity), jnp.int32).at[:, slot_c].add(q)
        return jax.lax.psum(qb, axis), res

    run = jax.shard_map(body, mesh=mesh,
                        in_specs=(P(None, axis), P(), P(axis), P(axis)),
                        out_specs=(P(), P(None, axis)),
                        check_vma=False)
    q_bufs, residuals = run(u_pad, q_keys, _pad_cols(plan.sel, width),
                            _pad_cols(plan.slot, width))
    return q_bufs, residuals[:, :d]
