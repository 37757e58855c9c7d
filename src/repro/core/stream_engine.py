"""The streaming chunked round engine: O(N·chunk) peak memory, bit-identical.

``aggregate_stack`` (the monolithic engine) materializes several [N, d]
temporaries per round — Gumbel score stacks, vote masks, dense quantization
buffers — which caps the round size this box can hold.  This module
restructures the same round as **chunk scans**: ``lax.scan`` over
``stream_chunk``-sized coordinate ranges whose carries are updated in
place, so the live set beyond inputs/outputs is O(N·chunk) + O(d).  The
block-compact phase 2 writes the residual over the (donated) input stack
itself and the delta into a flat (d,) carry; the topk-compact phase 2
keeps write-only carries (see :func:`_phase2_topk`).

Exactness (DESIGN.md §12) rests on three facts:

1. **Integer vote sums are associative** — per-chunk count accumulation
   cannot perturb phase 1.  Threshold voting is chunk-local once each
   client's max |u| is known (one extra max scan); Gumbel top-k voting
   needs a per-client *global* selection, so phase 1 streams over the
   *client* axis instead (O(d) per step), reusing the single-sort
   ``selection.topk_mask`` row computation the monolithic engine batches.
2. **The consensus selection is threshold-shaped** — ``build_round_plan``
   already derives the exact global count threshold by bisection plus the
   tie-break-by-index rule; the streaming engine reuses it verbatim and
   only adds the inverse ``slot`` map so each chunk knows its coordinates'
   compact-buffer positions without re-sorting.
3. **Chunks cover disjoint index ranges** — every consensus coordinate is
   compressed in exactly one chunk, so per-chunk scatters/updates commute
   and the aggregated integers match the monolithic path bit for bit.

Phase-2 uniforms are the one subtlety: the monolithic path draws d-sized
streams per client (block mode, fused Pallas mode), which a chunk scan
must *slice*, not re-draw — :func:`repro.core.streams.uniform_block`
reconstructs exactly the threefry counters of each chunk.

Entry points:

* :func:`aggregate_stream` — drop-in for :func:`repro.core.fediac
  .aggregate_stack` (same signature and return contract, bit-identical
  outputs, pinned in ``tests/test_stream_engine.py``).
* :func:`stream_compress_stack` — the phase-2 half only, returning the
  per-client compact buffers: what the packet dataplane
  (``repro.netsim``) feeds through the register windows.

``vote_chunk > 1`` (chunked vote bits) is not streamed; callers keep the
monolithic engine for that mode.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.obs.scopes import scope

from . import compaction, robust_agg, selection, voting
from .quantize import dequantize, quantize, scale_factor
from .round_plan import RoundPlan, build_round_plan
from .streams import uniform_block

__all__ = ["aggregate_stream", "stream_compress_stack", "DEFAULT_CHUNK"]

DEFAULT_CHUNK = 1 << 18  # coords per streamed chunk (~1 MiB f32 per client)


def _chunk_size(cfg, d: int, chunk: int | None = None) -> int:
    """Resolve the streamed chunk size: explicit arg > cfg.stream_chunk >
    default, aligned down to the block granule so blocks never straddle
    chunks (the block compaction's per-chunk locality invariant)."""
    c = int(chunk if chunk is not None
            else (getattr(cfg, "stream_chunk", 0) or DEFAULT_CHUNK))
    if cfg.compact_mode == "block":
        bs = int(cfg.block_size)
        c = max(bs, c - c % bs)
    return max(1, min(c, d))


def _scan_chunks(body, carry, d: int, chunk: int):
    """Drive ``body(carry, start, size) -> (carry, y)`` over [0, d):
    a ``lax.scan`` over the full chunks plus one trailing call for the
    remainder (d need not divide by the chunk size).  Returns
    ``(carry, ys_full, y_tail)`` — stacked scan outputs and the tail's."""
    nfull, tail = divmod(d, chunk)
    ys_full = y_tail = None
    if nfull:
        starts = jnp.arange(nfull, dtype=jnp.int32) * chunk
        carry, ys_full = jax.lax.scan(
            lambda c, s: body(c, s, chunk), carry, starts)
    if tail:
        carry, y_tail = body(carry, jnp.int32(nfull * chunk), tail)
    return carry, ys_full, y_tail


def _cat_coords(ys_full, y_tail):
    """Concatenate per-coordinate chunk outputs back into a (d,) vector."""
    parts = []
    if ys_full is not None:
        parts.append(ys_full.reshape(-1))
    if y_tail is not None:
        parts.append(y_tail)
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts)


def _fused(cfg) -> bool:
    """Mirror of ``fediac.phase2_compress``'s Pallas-kernel selection."""
    return bool(cfg.use_pallas) and cfg.vote_chunk == 1 \
        and cfg.compact_mode != "block"


# ---------------------------------------------------------------------------
# Phase 1: vote counts + global max, streamed
# ---------------------------------------------------------------------------

def _phase1_threshold(u_stack: jax.Array, cfg, chunk: int):
    """Chunked threshold voting: max scan, then an indicator-count scan.

    Bit-identical to ``_vote_counts_stack``'s threshold branch: the
    per-client tau needs only that client's global max |u| (associative),
    and the count at each coordinate is an integer sum over clients.
    """
    n, d = u_stack.shape
    k = max(1, min(cfg.k(d), d))

    def max_body(m, start, size):
        u_c = jax.lax.dynamic_slice(u_stack, (0, start), (n, size))
        return jnp.maximum(m, jnp.max(jnp.abs(u_c), axis=1)), None

    m_vec, _, _ = _scan_chunks(max_body, jnp.zeros((n,), u_stack.dtype),
                               d, chunk)
    tau = voting.vote_tau(m_vec, k, cfg.alpha)

    def count_body(carry, start, size):
        u_c = jax.lax.dynamic_slice(u_stack, (0, start), (n, size))
        votes = (jnp.abs(u_c) >= tau[:, None]).astype(jnp.uint8)
        return carry, votes.astype(jnp.int32).sum(axis=0)

    _, ys, yt = _scan_chunks(count_body, 0, d, chunk)
    return _cat_coords(ys, yt), jnp.max(m_vec)


def _phase1_topk(u_stack: jax.Array, cfg, vote_keys: jax.Array):
    """Client-streamed Gumbel top-k voting: one O(d) row at a time.

    The per-client selection is global over d (a chunk cannot know the
    row's k-th score), so this phase scans the *client* axis: peak live
    memory is one row's scores + the int32 counts, not the [N, d] stack.
    Row masks are ``selection.topk_mask`` — the same bit-identical
    single-sort computation ``topk_counts_stack`` batches — and integer
    count accumulation is order-invariant.
    """
    n, d = u_stack.shape
    k = min(cfg.k(d), d)

    def body(carry, xs):
        counts, m = carry
        u_row, kv = xs
        mask = selection.topk_mask(voting.vote_scores(u_row, kv), k)
        return (counts + mask.astype(jnp.int32),
                jnp.maximum(m, jnp.max(jnp.abs(u_row)))), None

    init = (jnp.zeros((d,), jnp.int32), jnp.zeros((), u_stack.dtype))
    (counts, m), _ = jax.lax.scan(body, init, (u_stack, vote_keys))
    return counts, m


# ---------------------------------------------------------------------------
# Phase 2: compress + aggregate + residual, one chunk at a time
# ---------------------------------------------------------------------------

def _topk_chunk(u_c, cfg, f, q_keys, plan: RoundPlan, uq_all, start, size, d):
    """One chunk of every client's topk-compact phase 2: (q, residual),
    both [N, size].  ``q`` is zero wherever the chunk coordinate is not a
    kept consensus coordinate — exactly the monolithic compact buffer's
    content at the matching slots."""
    dt = u_c.dtype
    sel_c = jax.lax.dynamic_slice(plan.sel, (start,), (size,))
    if _fused(cfg):
        from repro.kernels import ops as kops
        uni = jax.vmap(lambda kk: uniform_block(kk, start, size, d))(q_keys)
        q, res = kops.gather_quant_chunk(u_c, uni, sel_c, f)
        return q, res.astype(dt)
    slot_c = jax.lax.dynamic_slice(plan.slot, (start,), (size,))
    uni = jnp.take(uq_all, slot_c, axis=1)
    keep_c = sel_c.astype(jnp.float32)
    # replicate client_compress's exact cast chain (compact -> f32).
    gathered = ((u_c.astype(jnp.float32) * keep_c).astype(dt)
                ).astype(jnp.float32)
    q = quantize(gathered, f, uni)
    up = dequantize(q, f).astype(dt)
    vals = (up.astype(jnp.float32) * keep_c).astype(dt)
    return q, u_c - vals


def _phase2_topk(u_stack, cfg, f, q_keys, plan: RoundPlan, chunk: int):
    """Streamed topk-compact phase 2 for the in-memory engine: chunks are
    read from the (loop-invariant) input stack and written into
    **write-only** carries — the residual stack and the dense int32
    quantized-sum.  The compact buffer is a C-sized gather at the end — no
    d-sized scatters anywhere.

    Unlike :func:`_phase2_block` this path keeps a second [N, d] residual
    stack: a carry that is also sliced as the chunk source is one the TPU
    compiler updates in place, but XLA:CPU copies it whole on every scan
    step, and no measured configuration runs topk compaction on the chip
    to show which way pays off here."""
    n, d = u_stack.shape
    uq_all = None
    if not _fused(cfg):
        capacity = plan.idx.shape[0]
        uq_all = jax.vmap(
            lambda kk: jax.random.uniform(kk, (capacity,), jnp.float32)
        )(q_keys)

    def body(carry, start, size):
        qsum, resid = carry
        u_c = jax.lax.dynamic_slice(u_stack, (0, start), (n, size))
        q, res = _topk_chunk(u_c, cfg, f, q_keys, plan, uq_all, start, size, d)
        # client-axis close per chunk: the plain integer sum, or the §18
        # trimmed close (chunk-local — the trim is coordinate-wise)
        qagg, _ = robust_agg.client_sum(q, cfg)
        qsum = jax.lax.dynamic_update_slice(qsum, qagg, (start,))
        resid = jax.lax.dynamic_update_slice(resid, res, (0, start))
        return (qsum, resid), None

    (qsum_dense, residuals), _, _ = _scan_chunks(
        body, (jnp.zeros((d,), jnp.int32), jnp.zeros_like(u_stack)), d, chunk)
    summed = jnp.take(qsum_dense, plan.idx)
    kept = robust_agg.kept_count(cfg, n)
    delta = compaction.scatter_compact(summed, plan.idx, plan.keep,
                                       d).astype(jnp.float32) / (kept * f)
    return delta, residuals


def _phase2_block(u_stack, cfg, f, q_keys, plan: RoundPlan, chunk: int):
    """Streamed block-compact phase 2: with blocks never straddling chunks
    the whole round is chunk-local, and the compact/scatter round-trip
    collapses to ``where(keep, sum_i q_i, 0)`` per chunk (what
    ``block_scatter(sum block_compact(q_i))`` computes coordinate-wise).

    Both results are written in place.  The scan carries the input stack
    itself: each step reads its chunk from the carry and writes the
    chunk's residual back over it at the same offset, so under the
    caller's ``donate_argnums=0`` the residual output *is* the donated
    buffer — no second [N, d] stack, zero fill or final copy.  The delta
    rides in the carry as a flat (d,) vector written chunk by chunk, not
    as a stacked per-chunk scan output that has to be relaid and
    concatenated afterwards.  Each chunk is read before it is written and
    no step reads another's range, so the results are those of reading
    the invariant input.  The TPU compiler keeps the carry in place;
    XLA:CPU copies it on every scan step, a cost of the CPU backend only."""
    n, d = u_stack.shape
    dt = u_stack.dtype

    def body(carry, start, size):
        resid, delta = carry
        u_c = jax.lax.dynamic_slice(resid, (0, start), (n, size))
        keep_c = jax.lax.dynamic_slice(plan.keep_dense, (start,), (size,))
        uni = jax.vmap(lambda kk: uniform_block(kk, start, size, d))(q_keys)
        q = quantize(jnp.where(keep_c, u_c, 0.0), f, uni)
        res = (u_c - jnp.where(keep_c, dequantize(q, f), 0.0)).astype(dt)
        qagg, kept = robust_agg.client_sum(q, cfg)
        delta_c = jnp.where(keep_c, qagg,
                            0).astype(jnp.float32) / (kept * f)
        resid = jax.lax.dynamic_update_slice(resid, res, (0, start))
        delta = jax.lax.dynamic_update_slice(delta, delta_c, (start,))
        return (resid, delta), None

    (residuals, delta), _, _ = _scan_chunks(
        body, (u_stack, jnp.zeros((d,), jnp.float32)), d, chunk)
    return delta, residuals


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------

def _check_streamable(cfg):
    if cfg.vote_chunk != 1:
        raise NotImplementedError(
            "the streaming engine requires vote_chunk == 1 "
            "(chunked vote bits keep the monolithic engine)")


def aggregate_stream(u_stack: jax.Array, cfg, key: jax.Array, *, a=None,
                     chunk: int | None = None):
    """One FediAC round, chunk-streamed — bit-identical to
    :func:`repro.core.fediac.aggregate_stack` (same signature, same
    ``(delta, residuals, counts, TrafficStats)`` contract, all vote and
    compact modes).

    ``chunk`` overrides ``cfg.stream_chunk`` (block-size aligned).  Under
    ``jit`` with ``donate_argnums=(0,)`` the residual output reuses the
    ``u_stack`` buffer: the round's peak live memory is the donated stack
    plus O(N·chunk) scan temporaries plus O(d) vectors (counts, plan,
    quantized sum) — never a second [N, d] temporary.
    """
    from .fediac import round_traffic  # local import: fediac imports us

    n, d = u_stack.shape
    _check_streamable(cfg)
    chunk = _chunk_size(cfg, d, chunk)
    with scope("vote"):
        keys = jax.random.split(key, 2 * n)
        vote_keys, q_keys = keys[:n], keys[n:]
        if cfg.vote_mode == "threshold":
            counts, m = _phase1_threshold(u_stack, cfg, chunk)
        else:
            counts, m = _phase1_topk(u_stack, cfg, vote_keys)
    topk = cfg.compact_mode != "block"
    with scope("consensus"):
        f = scale_factor(cfg.bits, n, 1.0) / jnp.clip(m, 1e-12, None)
        plan = build_round_plan(counts, cfg, n, a=a, with_dense_mask=topk,
                                with_slot_map=topk)
    with scope("phase2"):
        if topk:
            delta, residuals = _phase2_topk(u_stack, cfg, f, q_keys, plan,
                                            chunk)
        else:
            delta, residuals = _phase2_block(u_stack, cfg, f, q_keys, plan,
                                             chunk)
    return delta, residuals, counts, round_traffic(cfg, d)


def stream_compress_stack(u_stack: jax.Array, cfg, f, q_keys: jax.Array,
                          plan: RoundPlan, *, chunk: int | None = None):
    """Chunk-streamed phase 2 returning per-client compact buffers:
    ``(q_bufs [N, C], residuals [N, d])``, bit-identical to
    ``vmap(phase2_compress(cfg))`` against the same plan.

    This is the packet-dataplane entry (DESIGN.md §9/§12): ``repro.netsim``
    needs each client's buffer — the register windows aggregate them packet
    by packet — so the dense quantized-sum shortcut of
    :func:`aggregate_stream` does not apply.  The topk path scatters each
    chunk's (disjoint) slots into the carried buffers; the block path's
    buffers are chunk-contiguous and simply concatenate.

    For topk mode ``plan`` must carry the dense mask and slot map
    (``build_round_plan(..., with_dense_mask=True, with_slot_map=True)``).
    """
    n, d = u_stack.shape
    _check_streamable(cfg)
    chunk = _chunk_size(cfg, d, chunk)
    dt = u_stack.dtype

    if cfg.compact_mode == "block":
        def body(resid, start, size):
            u_c = jax.lax.dynamic_slice(u_stack, (0, start), (n, size))
            keep_c = jax.lax.dynamic_slice(plan.keep_dense, (start,), (size,))
            pos_c = jax.lax.dynamic_slice(plan.pos, (start,), (size,))
            uni = jax.vmap(
                lambda kk: uniform_block(kk, start, size, d))(q_keys)
            q = quantize(jnp.where(keep_c, u_c, 0.0), f, uni)
            qb = jax.vmap(lambda qq: compaction.block_compact(
                qq, keep_c, pos_c, cfg.block_size, cfg.capacity_frac))(q)
            res = (u_c - jnp.where(keep_c, dequantize(q, f), 0.0)).astype(dt)
            resid = jax.lax.dynamic_update_slice(resid, res, (0, start))
            return resid, qb

        residuals, ys, yt = _scan_chunks(body, jnp.zeros_like(u_stack), d,
                                         chunk)
        parts = []
        if ys is not None:  # [nfull, N, cb*chunk/bs] -> [N, nfull*...]
            parts.append(ys.transpose(1, 0, 2).reshape(n, -1))
        if yt is not None:
            parts.append(yt)
        q_bufs = parts[0] if len(parts) == 1 else jnp.concatenate(parts,
                                                                  axis=1)
        return q_bufs, residuals

    capacity = plan.idx.shape[0]
    uq_all = None
    if not _fused(cfg):
        uq_all = jax.vmap(
            lambda kk: jax.random.uniform(kk, (capacity,), jnp.float32)
        )(q_keys)

    def body(carry, start, size):
        q_bufs, resid = carry
        u_c = jax.lax.dynamic_slice(u_stack, (0, start), (n, size))
        q, res = _topk_chunk(u_c, cfg, f, q_keys, plan, uq_all, start, size, d)
        slot_c = jax.lax.dynamic_slice(plan.slot, (start,), (size,))
        # q is 0 at every non-kept coordinate, so the dummy slot-0 adds
        # from masked coordinates are exact no-ops.
        q_bufs = q_bufs.at[:, slot_c].add(q)
        resid = jax.lax.dynamic_update_slice(resid, res, (0, start))
        return (q_bufs, resid), None

    (q_bufs, residuals), _, _ = _scan_chunks(
        body, (jnp.zeros((n, capacity), jnp.int32), jnp.zeros_like(u_stack)),
        d, chunk)
    return q_bufs, residuals
