"""Pallas TPU kernel: fused consensus select + stochastic quantize +
residual update — the whole FediAC phase-2 client round in one d-pass.

The jnp path walks the d-sized update three times per client (gather the
consensus coordinates, scatter the de-quantized upload back, subtract for
the error-feedback residual).  On TPU, scatters are the enemy; the fused
kernel uses the *dense* formulation instead: the round plan's selection
mask streams in alongside the update, and each (BLOCK_ROWS, LANES) tile
produces, in a single pass,

    q   = sel ? theta(f*u) : 0          (paper Eq. 1, unbiased rounding)
    res = u - (sel ? q/f : 0)           (new error-feedback state)

The C-sized consensus upload is then a cheap gather of ``q`` at the plan
indices — already quantized, no second pass over u.  Random uniforms are an
explicit input stream so the kernel is deterministic and bit-identical to
``ref.gather_quant_ref`` between interpret mode and hardware.

Block geometry: four (BLOCK_ROWS, LANES) operands = 32 KiB each per block;
double-buffered well under the ~16 MiB VMEM budget.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .platform import resolve_interpret
from .ref import LANES

BLOCK_ROWS = 8


def _gather_quant_kernel(f_ref, u_ref, uni_ref, sel_ref, q_ref, res_ref):
    f = f_ref[0, 0]
    u = u_ref[...].astype(jnp.float32)
    x = u * f
    lo = jnp.floor(x)
    q = (lo + (uni_ref[...] < (x - lo)).astype(jnp.float32)).astype(jnp.int32)
    sel = sel_ref[...] != 0
    q = jnp.where(sel, q, 0)
    q_ref[...] = q
    res_ref[...] = u - jnp.where(sel, q.astype(jnp.float32) / f, 0.0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_quant(u: jax.Array, uniforms: jax.Array, sel: jax.Array,
                 f: jax.Array, *, interpret: bool | None = None):
    """(R, LANES) fp32 u, U[0,1) uniforms, 0/1 sel mask, scalar f ->
    ((R, LANES) int32 q, (R, LANES) fp32 residual) in one pass."""
    r, l = u.shape
    assert l == LANES and r % BLOCK_ROWS == 0, (r, l)
    grid = (r // BLOCK_ROWS,)
    f2 = jnp.asarray(f, jnp.float32).reshape(1, 1)
    blk = lambda: pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0))
    return pl.pallas_call(
        _gather_quant_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), blk(), blk(), blk()],
        out_specs=(blk(), blk()),
        out_shape=(jax.ShapeDtypeStruct((r, LANES), jnp.int32),
                   jax.ShapeDtypeStruct((r, LANES), jnp.float32)),
        interpret=resolve_interpret(interpret),
    )(f2, u.astype(jnp.float32), uniforms, sel.astype(jnp.int32))
