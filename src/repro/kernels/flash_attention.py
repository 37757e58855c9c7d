"""Pallas TPU flash attention (forward): VMEM-resident online softmax.

The §Perf hillclimb showed attention score tiles are the single largest
HBM consumer of the pure-JAX training step (they are fusion outputs on the
XLA path).  This kernel keeps the (q_block, kv_block) tiles in VMEM: per
(batch, kv-head, group, q-block) program, an inner loop walks KV tiles with
running (max, sum, acc) carried in registers/VMEM — zero HBM traffic for
scores.  Supports causal masking, sliding windows and grouped-query
attention (KV heads never repeated).

Block geometry: q tile (QB, D), KV tiles (KB, D) sliced from the head's
full-sequence VMEM block.  With QB=512, KB=512, D<=256 the live set is
~1.5 MiB << 16 MiB VMEM.  The oracle is the pure-JAX blockwise path
(`repro.models.attention._blockwise_attention`), itself oracle-checked
against dense attention.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .platform import resolve_interpret

QB = 512
KB = 512
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, kb: int, causal: bool,
                  window: int, scale: float, q_base: int):
    qi = pl.program_id(2)                     # q-block index
    q = q_ref[0, 0].astype(jnp.float32)       # (QB, D)
    t = k_ref.shape[1]
    qb = q.shape[0]
    n_kv = t // kb

    q_start = qi * qb
    m0 = jnp.full((qb,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((qb,), jnp.float32)
    a0 = jnp.zeros((qb, v_ref.shape[-1]), jnp.float32)

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(j * kb, kb), :].astype(jnp.float32)    # (KB, D)
        v = v_ref[0, pl.ds(j * kb, kb), :].astype(jnp.float32)
        sc = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (qb, kb), 0)
        kpos = j * kb + jax.lax.broadcasted_iota(jnp.int32, (qb, kb), 1)
        ok = jnp.ones((qb, kb), jnp.bool_)
        if causal:
            ok &= kpos <= qpos
        if window > 0:
            ok &= kpos > qpos - window
        sc = jnp.where(ok, sc, NEG_INF)
        m_new = jnp.maximum(m, sc.max(axis=-1))
        p = jnp.exp(sc - m_new[:, None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ()))).astype(jnp.float32)
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(0, n_kv, body, (m0, l0, a0))
    o_ref[0, 0] = (acc / jnp.clip(l[:, None], 1e-30, None)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    interpret: bool | None = None) -> jax.Array:
    """q: (B, S, H, D); k, v: (B, T, Hk, D) with H % Hk == 0.

    Returns (B, S, H, Dv).  S % QB == 0 and T % KB == 0 required (the model
    layer pads; shapes in this framework are powers of two).
    """
    b, s, h, d = q.shape
    t, hk = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    g = h // hk
    assert s % QB == 0 and t % KB == 0, (s, t)
    nq = s // QB
    scale = 1.0 / math.sqrt(d)

    # layout: programs over (B*Hk, G, nq); K/V blocks indexed by head only
    qg = q.reshape(b, s, hk, g, d).transpose(0, 2, 3, 1, 4).reshape(b * hk, g, s, d)
    kt = k.transpose(0, 2, 1, 3).reshape(b * hk, t, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * hk, t, dv)

    out = pl.pallas_call(
        functools.partial(_flash_kernel, kb=KB, causal=causal, window=window,
                          scale=scale, q_base=0),
        grid=(b * hk, g, nq),
        in_specs=[
            pl.BlockSpec((1, 1, QB, d), lambda bh, gi, qi: (bh, gi, qi, 0)),
            pl.BlockSpec((1, t, d), lambda bh, gi, qi: (bh, 0, 0)),
            pl.BlockSpec((1, t, dv), lambda bh, gi, qi: (bh, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, QB, dv), lambda bh, gi, qi: (bh, gi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hk, g, s, dv), q.dtype),
        interpret=resolve_interpret(interpret),
    )(qg, kt, vt)

    return out.reshape(b, hk, g, s, dv).transpose(0, 3, 1, 2, 4).reshape(b, s, h, dv)
