"""Pallas TPU kernel: fused threshold-vote + bit-pack in one pass.

Phase 1 of FediAC's sort-free mode votes ``|u| >= tau`` (the Def.1
power-law threshold); the packed wire then ships one bit per (chunk of)
coordinate(s).  The seed path materialized the d-sized uint8 vote array
and re-read it to pack — this kernel compares and packs in a single VMEM
pass: scores stream in as (32*ROWS_PER_BLOCK, LANES) fp32 tiles, the
threshold sits in SMEM, and each group of 32 sublanes collapses to one
uint32 word row via VPU shift/or/add (the bitpack layout of
``kernels/ref.py``).  No intermediate d-array ever exists.

Block geometry: 1 MiB fp32 in -> 32 KiB uint32 out per grid step, same as
``bitpack.pack`` — comfortably double-buffered in the ~16 MiB VMEM budget.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .bitpack import pack_rows
from .platform import resolve_interpret
from .ref import GROUP, LANES

ROWS_PER_BLOCK = 8  # packed (uint32) rows produced per grid step


def _vote_pack_kernel(tau_ref, score_ref, out_ref):
    tau = tau_ref[0, 0]
    for g in range(ROWS_PER_BLOCK):  # static unroll
        rows = score_ref[g * GROUP:(g + 1) * GROUP, :] >= tau
        out_ref[g, :] = pack_rows(rows.astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("interpret",))
def vote_pack(scores: jax.Array, tau: jax.Array, *,
              interpret: bool | None = None) -> jax.Array:
    """(R, LANES) fp32 scores, scalar tau -> (R//32, LANES) uint32 words,
    bit r of word (g, l) holding ``scores[32 g + r, l] >= tau``."""
    r, l = scores.shape
    assert l == LANES and r % (GROUP * ROWS_PER_BLOCK) == 0, (r, l)
    grid = (r // (GROUP * ROWS_PER_BLOCK),)
    tau2 = jnp.asarray(tau, jnp.float32).reshape(1, 1)
    words = pl.pallas_call(
        _vote_pack_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((GROUP * ROWS_PER_BLOCK, LANES), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((ROWS_PER_BLOCK, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r // GROUP, LANES), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(tau2, scores.astype(jnp.float32))
    return jax.lax.bitcast_convert_type(words, jnp.uint32)
