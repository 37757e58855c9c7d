"""Pallas TPU kernel: fused unpack + popcount-accumulate of packed vote words.

The PS side of FediAC phase 1: given N clients' bit-packed vote arrays
(uint32 words), produce per-coordinate vote counts.  On TPU this is the
local reduction stage of the packed-bit all-gather variant (the beyond-paper
phase-1 schedule: all-gather d/8 bytes of packed bits instead of psum'ing
d uint8 counts — 8x fewer collective bytes when N is small).

Block geometry: (N, ROWS_PER_BLOCK, LANES) uint32 in -> counts
(ROWS_PER_BLOCK*32, LANES) int32 out.  N is the client-axis size (<= 64),
so a block is N*8*1024*4 B = 32 KiB * N — fits VMEM for any realistic N.

The kernel accumulates **bit planes**: for each of the 32 bit positions it
shifts/masks the (N, R, LANES) word block and reduces over clients, so the
largest live tensor is one (R, GROUP, LANES) int32 plane stack — the same
size as the output block.  (The seed version ``jnp.repeat``-ed the words to
(N, R*32, LANES) first: a 32x VMEM blow-up that overflowed the ~16 MiB
budget beyond N ~ 16.)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .platform import resolve_interpret
from .ref import GROUP, LANES

ROWS_PER_BLOCK = 8


def _popcount_kernel(words_ref, out_ref):
    w = words_ref[...]                         # (N, ROWS_PER_BLOCK, LANES)
    # bit-plane accumulation: per bit position r, the client-reduced plane
    # is (ROWS_PER_BLOCK, LANES) — VPU shift/and/add only, no repeat.  The
    # words arrive bitcast to int32 (Mosaic reduces no unsigned ints); an
    # arithmetic shift leaves bit r in place, so ``& 1`` reads it exactly.
    planes = [((w >> r) & 1).sum(axis=0)
              for r in range(GROUP)]           # static unroll
    acc = jnp.stack(planes, axis=1)            # (ROWS, GROUP, LANES)
    out_ref[...] = acc.reshape(-1, acc.shape[-1])


@functools.partial(jax.jit, static_argnames=("interpret",))
def popcount_accum(words_stack: jax.Array, *,
                   interpret: bool | None = None) -> jax.Array:
    """(N, G, LANES) uint32 packed votes -> (G*32, LANES) int32 counts."""
    n, g, l = words_stack.shape
    assert l == LANES and g % ROWS_PER_BLOCK == 0, (n, g, l)
    grid = (g // ROWS_PER_BLOCK,)
    return pl.pallas_call(
        _popcount_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((n, ROWS_PER_BLOCK, LANES), lambda i: (0, i, 0))],
        out_specs=pl.BlockSpec((GROUP * ROWS_PER_BLOCK, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((g * GROUP, LANES), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(jax.lax.bitcast_convert_type(words_stack, jnp.int32))
