"""Baseline aggregation collectives for the production mesh path.

The FL simulator (``core.baselines``) prices the baselines through the
switch queuing model; these are their shard_map forms, so the dry-run can
compare collective payloads at full model scale:

* ``switchml_allreduce`` — SwitchML [NSDI'21]: dense unbiased integer
  quantization; the psum wire dtype is the narrowest integer that can hold
  the N-client sum (b + ceil(log2 N) bits).
* ``topk_allreduce`` — per-client Top-k *without* consensus.  On a switch
  this costs index-alignment state; on a TPU all-reduce it is starker: the
  sparse vector must be scattered back to dense before the psum, so the
  wire cost equals dense FedAvg.  Sparsity without consensus does not
  compress a collective — the motivation example (paper Sec. III-B) in
  collective form.

Both share the ``(u, residual, key, cfg, client_axes)`` signature of
``fediac_allreduce`` and plug into ``ArchConfig.aggregator``.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

from .fediac import FediACConfig
from .quantize import dequantize, quantize, scale_factor

__all__ = ["switchml_allreduce", "topk_allreduce"]


def _axes(client_axes):
    return (client_axes,) if isinstance(client_axes, str) else tuple(client_axes)


def _n_clients(axes):
    n = 1
    for ax in axes:
        n *= jax.lax.axis_size(ax)
    return n


def switchml_allreduce(u: jax.Array, residual: jax.Array, key: jax.Array,
                       cfg: FediACConfig | None = None,
                       client_axes: str | Sequence[str] = "data"):
    """Dense b-bit integer aggregation (no sparsification, no residual)."""
    axes = _axes(client_axes)
    n = _n_clients(axes)
    bits = cfg.bits if cfg is not None else 12
    u = (u + residual).astype(jnp.float32)
    m = jax.lax.pmax(jnp.max(jnp.abs(u)), axes)
    f = scale_factor(bits, n, 1.0) / jnp.clip(m, 1e-12, None)
    uniforms = jax.random.uniform(
        jax.random.fold_in(key, jax.lax.axis_index(axes[0])), u.shape)
    q = quantize(u, f, uniforms)
    # narrowest wire dtype that holds the N-client sum of b-bit values
    import math
    need = bits + max(1, math.ceil(math.log2(max(n, 2))))
    wire = jnp.int16 if need <= 15 else jnp.int32
    summed = jax.lax.psum(q.astype(wire), axes)
    mean = dequantize(summed.astype(jnp.int32), f) / n
    return mean, jnp.zeros_like(residual)


def topk_allreduce(u: jax.Array, residual: jax.Array, key: jax.Array,
                   cfg: FediACConfig | None = None,
                   client_axes: str | Sequence[str] = "data"):
    """Per-client Top-k with error feedback, aggregated densely (indices
    differ per client, so the psum payload cannot shrink)."""
    axes = _axes(client_axes)
    n = _n_clients(axes)
    k_frac = cfg.k_frac if cfg is not None else 0.05
    d = u.shape[-1]
    k = max(1, int(round(k_frac * d)))
    u = (u + residual).astype(jnp.float32)
    _, idx = jax.lax.top_k(jnp.abs(u), k)
    mask = jnp.zeros((d,), jnp.float32).at[idx].set(1.0)
    sparse = u * mask
    new_residual = (u - sparse).astype(residual.dtype)
    mean = jax.lax.psum(sparse, axes) / n     # dense wire: the alignment tax
    return mean, new_residual
