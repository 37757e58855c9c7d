"""Robust client-axis aggregation: trimmed-mean / median consensus slots
(DESIGN.md §18).

FediAC's phase 2 sums quantized int32 contributions blindly — one
sign-flipped or scaled client perturbs every consensus slot it touches.
This module is the switch-side answer: coordinate-wise order-statistic
filtering *within* each consensus slot, selected by
``FediACConfig(robust_agg=...)``:

* ``"sum"``  — the paper's plain integer addition (the default; every
  call site Python-gates on it, so the sum program is not merely equal
  to the pre-robust code — it is unchanged);
* ``"trim"`` — drop the ``t`` smallest and ``t`` largest live values of
  each slot, ``t = floor(trim_frac * n_live)`` clamped so at least one
  value survives, and aggregate the rest;
* ``"median"`` — maximal trim, ``t = (n_live - 1) // 2``: the middle
  value (odd ``n_live``) or the two middle values (even).

The guarantee (pinned by ``tests/test_robust.py`` property tests): with
at most ``f`` adversarial values per slot and ``t >= f``, every kept
value — hence the kept mean — lies within the honest values' range.

Tie-break rule, exact by construction: values sort ascending with a
*stable* argsort, so equal values keep client-index order, and dead rows
(non-committed clients) carry a dtype-max sentinel that places them
strictly after every live value.  The aggregation stays in the int32
register domain — the switch keeps per-slot order statistics in integer
registers and the host divides by the kept count at decompression, just
as the plain path divides the register sum by ``n``.

All helpers accept traced scalars (``trim_frac``, ``n_live``) so
attack x defense sweep cells batch on the fleet axis (DESIGN.md §13).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.obs.scopes import scope

__all__ = ["ROBUST_AGG_MODES", "trim_count", "trimmed_sum", "client_sum",
           "kept_count"]

#: registered robust aggregation modes (FediACConfig.robust_agg)
ROBUST_AGG_MODES = ("sum", "trim", "median")


def trim_count(mode: str, trim_frac, n_live):
    """Per-side trim depth ``t`` for ``n_live`` live values.

    ``trim`` takes ``floor(trim_frac * n_live)``; ``median`` is the
    maximal trim.  Both clamp to ``(n_live - 1) // 2`` so at least one
    value survives per slot.  ``trim_frac`` / ``n_live`` may be traced.
    """
    n_live = jnp.asarray(n_live, jnp.int32)
    max_t = jnp.maximum(n_live - 1, 0) // 2
    if mode == "median":
        return max_t
    t = jnp.floor(jnp.float32(trim_frac)
                  * n_live.astype(jnp.float32)).astype(jnp.int32)
    return jnp.clip(t, 0, max_t)


def _sentinel(dtype) -> jax.Array:
    dt = jnp.dtype(dtype)
    if jnp.issubdtype(dt, jnp.integer):
        return jnp.asarray(jnp.iinfo(dt).max, dt)
    return jnp.asarray(jnp.inf, dt)


def trimmed_sum(values: jax.Array, live: jax.Array, t):
    """Sum each slot's live values with the ``t`` lowest and ``t``
    highest removed.

    ``values``: ``[N, C]`` per-client slot contributions (int32 on the
    wire paths); ``live``: bool ``[N]`` commit mask; ``t``: per-side trim
    depth (traced int32 scalar, see :func:`trim_count`).  Returns
    ``(kept_sum [C], kept int32 scalar = n_live - 2t)``.

    Rank algebra, fixed-shape: dead rows take a dtype-max sentinel (no
    quantized value reaches int32 max — ``|q| <= 2^(b-1)``), a stable
    ascending argsort gives each element its per-slot rank with ties
    broken by client index, and the keep mask is ``t <= rank <
    n_live - t``.  At ``t == 0`` the keep mask is exactly ``live`` and
    the kept sum equals the masked ``jnp.sum`` of the plain path.
    """
    masked = jnp.where(live[:, None], values, _sentinel(values.dtype))
    order = jnp.argsort(masked, axis=0)       # stable: ties keep row order
    rank = jnp.argsort(order, axis=0)         # inverse permutation per slot
    n_live = jnp.sum(live.astype(jnp.int32))
    t = jnp.asarray(t, jnp.int32)
    keep = live[:, None] & (rank >= t) & (rank < n_live - t)
    kept_sum = jnp.sum(jnp.where(keep, values, 0), axis=0)
    return kept_sum, n_live - 2 * t


def client_sum(q: jax.Array, cfg):
    """Aggregate the client axis of one chunk of per-client quantized
    contributions under ``cfg.robust_agg``.

    The single seam every in-memory engine sums through (monolithic,
    stream, sharded — ``engines.py`` dispatch): ``q`` is ``[N, chunk]``
    with the *full* client axis present, so the coordinate-wise trim is
    chunk-local; it runs in the ``register_fold`` device scope.  Returns
    ``(aggregated [chunk], kept)`` where ``kept`` is the Python int ``N``
    in sum mode (the call site's ``/(n * f)`` denominator is the
    pre-robust expression, bitwise) and a traced int32 scalar otherwise.
    """
    n = q.shape[0]
    with scope("register_fold"):
        if cfg.robust_agg == "sum":
            return q.sum(axis=0), n
        t = trim_count(cfg.robust_agg, cfg.trim_frac, n)
        return trimmed_sum(q, jnp.ones((n,), bool), t)


def kept_count(cfg, n: int):
    """The per-slot kept count of an all-live ``n``-client round — the
    aggregation denominator.  Python int ``n`` in sum mode (the call
    site's pre-robust ``/(n * f)`` expression survives bitwise), traced
    int32 otherwise.  For engines whose kept sums are assembled away
    from their denominators (the stream scan, the shard chunks)."""
    if cfg.robust_agg == "sum":
        return n
    return n - 2 * trim_count(cfg.robust_agg, cfg.trim_frac, n)
