"""Pallas TPU kernels: pack/unpack 0-1 vote arrays into uint32 words.

Phase 1 of FediAC represents each (chunk of) model-update coordinate(s) with
a single bit.  These kernels build/unbuild that wire format.  Packing runs
along the sublane axis (32 consecutive rows -> one uint32 row), so each
VMEM block stays lane-parallel: VPU shift/or/add only, no intra-lane
reshapes, and every slice touched is contiguous.

Block geometry: input tiles of (32*ROWS_PER_BLOCK, LANES) int32 masks map to
output tiles of (ROWS_PER_BLOCK, LANES) uint32 words.  LANES=1024 keeps the
lane dim a multiple of the 128-lane VREG; ROWS_PER_BLOCK=8 gives 256
sublanes in / 8 out, i.e. 1 MiB in + 32 KiB out per block — comfortably
inside the ~16 MiB VMEM budget with double buffering.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .platform import resolve_interpret
from .ref import GROUP, LANES

ROWS_PER_BLOCK = 8  # packed (uint32) rows produced per grid step


def pack_rows(bits):
    """(GROUP, LANES) 0/1 int32 -> (LANES,) int32 word bit pattern.

    Mosaic reduces no unsigned integers, so the fold runs in int32: the
    shifted rows hold disjoint bits, whose two's-complement sum is their
    OR (bit 31 included), i.e. the uint32 word's bits exactly.  Callers
    bitcast the int32 result to uint32 outside the kernel.
    """
    shifts = jax.lax.broadcasted_iota(jnp.int32, bits.shape, 0)
    return (bits << shifts).sum(axis=0)


def _pack_kernel(mask_ref, out_ref):
    for g in range(ROWS_PER_BLOCK):  # static unroll
        out_ref[g, :] = pack_rows(mask_ref[g * GROUP:(g + 1) * GROUP, :])


def _unpack_kernel(words_ref, out_ref):
    w = words_ref[...]                       # (ROWS_PER_BLOCK, LANES)
    wr = jnp.repeat(w, GROUP, axis=0)        # (ROWS_PER_BLOCK*32, LANES)
    r = jax.lax.broadcasted_iota(jnp.uint32, wr.shape, 0) % jnp.uint32(GROUP)
    out_ref[...] = ((wr >> r) & jnp.uint32(1)).astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=("interpret",))
def pack(mask: jax.Array, *, interpret: bool | None = None) -> jax.Array:
    """(R, LANES) 0/1 int -> (R//32, LANES) uint32.  R % (32*8) == 0."""
    r, l = mask.shape
    assert l == LANES and r % (GROUP * ROWS_PER_BLOCK) == 0, (r, l)
    grid = (r // (GROUP * ROWS_PER_BLOCK),)
    words = pl.pallas_call(
        _pack_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((GROUP * ROWS_PER_BLOCK, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((ROWS_PER_BLOCK, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r // GROUP, LANES), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(mask.astype(jnp.int32))
    return jax.lax.bitcast_convert_type(words, jnp.uint32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def unpack(words: jax.Array, *, interpret: bool | None = None) -> jax.Array:
    """(G, LANES) uint32 -> (G*32, LANES) uint8."""
    g, l = words.shape
    assert l == LANES and g % ROWS_PER_BLOCK == 0, (g, l)
    grid = (g // ROWS_PER_BLOCK,)
    return pl.pallas_call(
        _unpack_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((ROWS_PER_BLOCK, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((GROUP * ROWS_PER_BLOCK, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((g * GROUP, LANES), jnp.uint8),
        interpret=resolve_interpret(interpret),
    )(words)
