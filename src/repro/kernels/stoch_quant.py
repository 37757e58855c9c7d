"""Pallas TPU kernel: fused scale + unbiased stochastic rounding (paper Eq. 1).

q = floor(f*u) + [uniform < frac(f*u)], elementwise on the VPU.  The scale
``f`` arrives as a (1,1) scalar in SMEM; random uniforms are an explicit
input stream (drawn by the host PRNG) so the kernel is deterministic given
its inputs and bit-identical between interpret mode and hardware.

Block geometry: (BLOCK_ROWS, LANES) fp32 tiles: 8*1024*4 = 32 KiB per
operand per block; three operands triple-buffered still < 1 MiB of VMEM.
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


def _quant_kernel(f_ref, u_ref, uni_ref, out_ref):
    x = u_ref[...].astype(jnp.float32) * f_ref[0, 0]
    lo = jnp.floor(x)
    up = (uni_ref[...] < (x - lo)).astype(jnp.float32)
    out_ref[...] = (lo + up).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def stoch_quant(u: jax.Array, uniforms: jax.Array, f: jax.Array,
                *, interpret: bool | None = None) -> jax.Array:
    """(R, LANES) fp32, (R, LANES) U[0,1), scalar f -> (R, LANES) int32."""
    r, l = u.shape
    assert l == LANES and r % BLOCK_ROWS == 0, (r, l)
    grid = (r // BLOCK_ROWS,)
    f2 = jnp.asarray(f, jnp.float32).reshape(1, 1)
    return pl.pallas_call(
        _quant_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
            pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, LANES), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(f2, u.astype(jnp.float32), uniforms)
