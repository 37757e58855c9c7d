"""A plain FediAC aggregation across clients, with the semantics of the
program's cross-chip allreduce, over the stacked float32 ``[N, d]``
uploads; it imports nothing of ``repro``.

Client ``i`` of a round with key ``key``, holding its upload ``u_i``
(its local update plus the residual it carried):

1. folds its index into the key, ``k_i = fold_in(key, i)``, and splits
   it into a vote key and a quantization key;
2. votes every coordinate with ``|u_i| >= max|u_i| * k**alpha``
   (threshold vote); the switch adds the votes: ``counts``;
3. the scale is ``f = (2**(b-1) - N) / (N * max_i max|u_i|)``;
4. consensus: in each block of ``block_size`` coordinates, the first
   ``round(capacity_frac * block_size)`` whose count reaches ``a``;
5. block compaction packs those coordinates into the upload buffer;
6. the switch adds the integers ``q_i = floor(f u_i) + [uniform <
   frac(f u_i)]`` (uniforms by coordinate, from the quantization key);
7. everyone applies the mean ``sum q / (N f)`` at the kept coordinates,
   and client ``i`` keeps ``u_i - q_i / f`` there, ``u_i`` elsewhere.

Steps 2-4 and 6-7 are the round reference's (``fediac_round``), which
has the same semantics for one client row; the compaction of step 5 and
the de-compaction after the sum are a bijection between the kept
coordinates and the buffer's slots, so the reference adds the integers
at the coordinates themselves. ``summed`` < N adds only the first
``summed`` clients' integers (a fault that must fail the comparison).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from bench.reference import fediac_round as fr


@partial(jax.jit, donate_argnums=1)
def _add(u, residual):
    return u + residual


def allreduce(u, residual, key, p: tuple, summed: int | None = None):
    """``(mean[d], new residual[N, d])`` of uploads ``u + residual``
    (float32 ``[N, d]``; ``residual``'s buffer is donated). ``p`` is
    :func:`fediac_round.params` of the traffic mix."""
    n, d = u.shape
    u = _add(u, residual)
    qkeys = [jax.random.split(jax.random.fold_in(key, i))[1] for i in range(n)]
    counts = jnp.zeros((d,), jnp.int32)
    m = jnp.zeros((), jnp.float32)
    for i in range(n):
        counts, m = fr._vote(u, i, qkeys[i], counts, m, p)
    kept, slot, f = fr._consensus(counts, m, p, n)
    qsum = jnp.zeros((d,), jnp.int32)
    for i in range(n):
        row, q_all = fr._send(u, i, qkeys[i], kept, slot, f, qsum, p)
        if summed is None or i < summed:
            qsum = q_all
        u = fr._put(u, i, row)
    return fr._delta(qsum, kept, f, n), u
