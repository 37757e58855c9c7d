"""Jit'd public wrappers around the Pallas kernels.

Handles the flat-vector <-> (rows, LANES) tiling and padding.
``interpret=None`` leaves the choice to the kernels
(``platform.resolve_interpret``): compiled on a TPU, interpreted elsewhere.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import bitpack, gather_quant, ref, stoch_quant, vote_pack, vote_popcount
from .ref import GROUP, LANES

_TILE = GROUP * bitpack.ROWS_PER_BLOCK * LANES  # flat elements per pack grid step


def _to_rows(flat: jax.Array, multiple: int, pad_value=0):
    """Pad a flat vector to a (rows, LANES) matrix with rows % multiple == 0."""
    d = flat.shape[-1]
    rows = -(-d // LANES)
    rows += (-rows) % multiple
    pad = rows * LANES - d
    return (jnp.pad(flat, (0, pad), constant_values=pad_value)
            .reshape(rows, LANES), d)


def pack_votes(mask_flat: jax.Array, *, interpret: bool | None = None) -> jax.Array:
    """Flat 0/1 votes (d,) -> packed uint32 (ceil-padded) words, flat."""
    m2, _ = _to_rows(mask_flat, GROUP * bitpack.ROWS_PER_BLOCK)
    return bitpack.pack(m2, interpret=interpret).reshape(-1)


def unpack_votes(words_flat: jax.Array, d: int, *, interpret: bool | None = None) -> jax.Array:
    """Packed uint32 words (flat) -> 0/1 uint8 votes (d,)."""
    w2 = words_flat.reshape(-1, LANES)
    out = bitpack.unpack(w2, interpret=interpret).reshape(-1)
    return out[:d]


def count_votes(words_stack_flat: jax.Array, d: int, *, interpret: bool | None = None) -> jax.Array:
    """(N, W) packed uint32 -> (d,) int32 vote counts (PS phase-1 reduce)."""
    n = words_stack_flat.shape[0]
    w3 = words_stack_flat.reshape(n, -1, LANES)
    out = vote_popcount.popcount_accum(w3, interpret=interpret).reshape(-1)
    return out[:d]


def quantize_flat(u_flat: jax.Array, uniforms_flat: jax.Array, f,
                  *, interpret: bool | None = None) -> jax.Array:
    """Flat fp32 (d,) -> flat int32 (d,), Eq. 1 with scale f."""
    u2, d = _to_rows(u_flat, stoch_quant.BLOCK_ROWS)
    uni2, _ = _to_rows(uniforms_flat, stoch_quant.BLOCK_ROWS)
    out = stoch_quant.stoch_quant(u2, uni2, f, interpret=interpret)
    return out.reshape(-1)[:d]


def pack_votes_threshold(scores_flat: jax.Array, tau,
                         *, interpret: bool | None = None) -> jax.Array:
    """Fused phase-1 wire build: flat scores (d,) -> packed uint32 words of
    the mask ``scores >= tau``, with no intermediate d-sized vote array.
    Padding lanes get -inf so they can never vote."""
    s2, _ = _to_rows(scores_flat, GROUP * vote_pack.ROWS_PER_BLOCK,
                     pad_value=-jnp.inf)
    return vote_pack.vote_pack(s2, tau, interpret=interpret).reshape(-1)


def gather_quant_chunk(u_chunk: jax.Array, uniforms_chunk: jax.Array,
                       sel_chunk: jax.Array, f,
                       *, interpret: bool | None = None):
    """Chunk-granular fused phase 2: all N clients' (L,) coordinate slice
    of one round in a single invocation — ``(u [N, L], uniforms [N, L],
    shared sel [L], f) -> (q int32 [N, L], residual fp32 [N, L])``.

    This is the streaming engine's grid step (DESIGN.md §12): the kernel
    is elementwise per coordinate, so chunk invocations are bit-identical
    to slicing one full-d ``gather_quant_flat`` call — provided the
    caller feeds the *sliced* uniforms of the full-d stream
    (``repro.core.streams.uniform_block``), not fresh draws.
    """
    return jax.vmap(lambda u, uni: gather_quant_flat(
        u, uni, sel_chunk, f, interpret=interpret))(u_chunk, uniforms_chunk)


def gather_quant_flat(u_flat: jax.Array, uniforms_flat: jax.Array,
                      sel_flat: jax.Array, f,
                      *, interpret: bool | None = None):
    """Fused phase-2 client round: flat (u, uniforms, sel mask, f) ->
    (q_dense int32 (d,), residual fp32 (d,)) in one pass over u."""
    u2, d = _to_rows(u_flat, gather_quant.BLOCK_ROWS)
    uni2, _ = _to_rows(uniforms_flat, gather_quant.BLOCK_ROWS)
    sel2, _ = _to_rows(sel_flat, gather_quant.BLOCK_ROWS)
    q2, res2 = gather_quant.gather_quant(u2, uni2, sel2, f,
                                         interpret=interpret)
    return q2.reshape(-1)[:d], res2.reshape(-1)[:d]


# jnp fallbacks with identical signatures (used in shape-polymorphic paths
# where Pallas padding would be wasteful, e.g. tiny smoke configs).
def quantize_flat_ref(u_flat, uniforms_flat, f):
    return ref.stoch_quant_ref(u_flat, uniforms_flat, jnp.float32(f))
