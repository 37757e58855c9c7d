"""A plain FediAC round (paper Algo. 1), written from the paper and not
from the program: it imports nothing of ``repro``.

One round over the stacked client updates ``u = g + r`` (float32 [N, d]):

1. phase 1, vote. ``topk``: client i votes the k coordinates of largest
   ``log|u_i| + Gumbel`` (sampling without replacement in proportion to
   |u_i|); ``threshold``: client i votes every coordinate with
   ``|u_i| >= max|u_i| * k**alpha`` (the paper's Def. 1 power law).
   The switch adds the votes: ``counts``.
2. scale ``f = (2**(b-1) - N) / (N * max|u|)``.
3. consensus. ``topk``: the C coordinates of largest count (ties: lower
   index first), kept where the count reaches ``a``; slot s of the
   upload is the s-th of them in that order. ``block``: in each block of
   ``block_size`` coordinates, the first ``round(capacity_frac *
   block_size)`` with a count of at least ``a``.
4. phase 2: each client sends ``q = floor(f u) + [uniform < frac(f u)]``
   at the kept coordinates; the switch adds the integers; everyone
   applies ``delta = sum q / (N f)``, and keeps ``u - q / f`` as its
   residual (error feedback).

The random streams are JAX's own: ``split(key, 2N)`` gives the vote keys
then the quantization keys; a client's Gumbel draw is ``gumbel(key_i,
(d,))``; its uniforms are ``uniform(key_i, (C,))`` by upload slot in topk
mode and ``uniform(key_i, (d,))`` by coordinate in block mode.

The exact top-k is a 32-step bisection on the order-preserving integer
image of the float scores: no sort, and memory of a few d-vectors. The
round runs one client row at a time, so it needs the [N, d] residual
(updated in place) and O(d) more.

``work_dtype`` is the precision the round computes in; float32 is the
reference, and anything else is a control that must fail the comparison.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def count_k(frac: float, d: int) -> int:
    return max(1, int(round(frac * d)))


def _order_key(x):
    """uint32 whose unsigned order is the float order of ``x``."""
    b = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    return jnp.where(b >> 31 == 1, ~b, b | jnp.uint32(0x80000000))


def _largest(key, k: int, bits: int):
    """Mask of the k largest entries of the unsigned ``key``, ties to the
    lower index: the k-th largest value by MSB-first bisection, then every
    entry above it and the first entries equal to it."""
    def step(i, acc):
        cand = acc | (jnp.asarray(1, key.dtype) << (bits - 1 - i).astype(key.dtype))
        return jnp.where(jnp.sum((key >= cand).astype(jnp.int32)) >= k, cand, acc)

    t = jax.lax.fori_loop(0, bits, step, jnp.zeros((), key.dtype))
    gt = key > t
    eq = key == t
    need = k - jnp.sum(gt.astype(jnp.int32))
    rank = jnp.cumsum(eq.astype(jnp.int32)) - eq.astype(jnp.int32)
    return gt | (eq & (rank < need))


@partial(jax.jit, static_argnames=("p",))
def _vote(u, i, vkey, counts, m, p):
    """Client i's phase 1, added to the switch's ``counts`` and max."""
    vote_mode, k_frac, alpha, wdt = p[0], p[2], p[6], jnp.dtype(p[8])
    d = u.shape[1]
    k = count_k(k_frac, d)
    x = u[i].astype(wdt)
    if vote_mode == "topk":
        logw = jnp.log(jnp.clip(jnp.abs(x).astype(jnp.float32), 1e-30, None))
        score = (logw + jax.random.gumbel(vkey, (d,), jnp.float32)).astype(wdt)
        mask = _largest(_order_key(score), k, 32)
    else:
        tau = jnp.max(jnp.abs(x)) * jnp.float32(k) ** jnp.float32(alpha)
        mask = jnp.abs(x) >= tau
    return (counts + mask.astype(jnp.int32),
            jnp.maximum(m, jnp.max(jnp.abs(x)).astype(jnp.float32)))


@partial(jax.jit, static_argnames=("p", "n"))
def _consensus(counts, m, p, n):
    """(kept mask, upload slot of each kept coordinate or None, scale f)."""
    compact_mode, cap_frac, a, bits, block_size = p[1], p[3], p[4], p[5], p[7]
    d = counts.shape[0]
    f = jnp.float32((2.0 ** (bits - 1) - n) / n) / jnp.clip(m, 1e-12, None)
    if compact_mode == "topk":
        cap = min(count_k(cap_frac, d), d)
        sel = _largest(counts.astype(jnp.uint32), cap, max(int(n).bit_length(), 1))
        kept = sel & (counts >= a)
        # slot: count descending, then index; one count level at a time
        def level(j, carry):
            slot, above = carry
            at = kept & (counts == n - j)
            rank = jnp.cumsum(at.astype(jnp.int32)) - 1
            return (jnp.where(at, above + rank, slot),
                    above + jnp.sum(at.astype(jnp.int32)))

        slot, _ = jax.lax.fori_loop(0, n - a + 1, level,
                                    (jnp.zeros((d,), jnp.int32), jnp.int32(0)))
        return kept, slot, f
    nb = -(-d // block_size)
    cb = count_k(cap_frac, block_size)
    s = jnp.pad(counts >= a, (0, nb * block_size - d)).reshape(nb, block_size)
    pos = jnp.cumsum(s.astype(jnp.int32), axis=1) - s.astype(jnp.int32)
    return (s & (pos < cb)).reshape(-1)[:d], None, f


@partial(jax.jit, static_argnames=("p",))
def _send(u, i, qkey, kept, slot, f, qsum, p):
    """Client i's phase 2: its residual row, and its integers added to the
    switch's ``qsum``."""
    cap_frac, wdt = p[3], jnp.dtype(p[8])
    d = u.shape[1]
    if slot is None:
        uni = jax.random.uniform(qkey, (d,), jnp.float32)
    else:
        cap = min(count_k(cap_frac, d), d)
        uni = jnp.take(jax.random.uniform(qkey, (cap,), jnp.float32), slot)
    x = u[i].astype(wdt)
    xf = x.astype(jnp.float32) * f
    lo = jnp.floor(xf)
    q = (lo + (uni < xf - lo).astype(jnp.float32)).astype(jnp.int32)
    q = jnp.where(kept, q, 0)
    back = jnp.where(kept, (q.astype(jnp.float32) / f).astype(wdt), 0)
    return (x - back).astype(jnp.float32), qsum + q


@partial(jax.jit, donate_argnums=(0,))
def _put(u, i, row):
    """Row i of ``u`` overwritten in place. A program of its own: where
    the row is read and written in one program, XLA copies the stack."""
    return jax.lax.dynamic_update_index_in_dim(u, row, i, 0)


@partial(jax.jit, static_argnames=("n",))
def _delta(qsum, kept, f, n):
    return jnp.where(kept, qsum, 0).astype(jnp.float32) / (n * f)


def round_step(u, key, p):
    """One round over the uploads ``u`` (float32 [N, d]; its buffer is
    donated and becomes the residual): ``(delta[d], residual[N, d],
    counts[d])``. ``p`` is a hashable tuple of the round's parameters
    (see :func:`params`). Each client's step is a program of its own, so
    that no more than one row's temporaries are ever live."""
    n, d = u.shape
    keys = jax.random.split(key, 2 * n)
    counts = jnp.zeros((d,), jnp.int32)
    m = jnp.zeros((), jnp.float32)
    for i in range(n):          # phase 1; the switch adds the votes
        counts, m = _vote(u, i, keys[i], counts, m, p)
    kept, slot, f = _consensus(counts, m, p, n)
    qsum = jnp.zeros((d,), jnp.int32)
    for i in range(n):          # phase 2; the switch adds the integers
        row, qsum = _send(u, i, keys[n + i], kept, slot, f, qsum, p)
        u = _put(u, i, row)
    return _delta(qsum, kept, f, n), u, counts


def params(traffic: dict, work_dtype: str = "float32") -> tuple:
    """The hashable round parameters of a traffic mix."""
    return (traffic["vote_mode"], traffic["compact_mode"],
            float(traffic["k_frac"]), float(traffic["capacity_frac"]),
            int(traffic["a"]), int(traffic["bits"]),
            float(traffic.get("alpha", -1.0)),
            int(traffic.get("block_size", 4096)), work_dtype)
