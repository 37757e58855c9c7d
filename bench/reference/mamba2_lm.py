"""A plain Mamba-2 language model (arXiv:2405.21060), its loss and its
gradients, and one federated client's local training; written from the
paper and not from the program: it imports nothing of ``repro``.

The model, per layer ``l`` (pre-norm residual, no MLP):

    h = rmsnorm(x) * ln1
    xin, z, B, C, dt_raw = h W_x, h W_z, h W_B, h W_C, h W_dt
    xin, B, C = silu(causal depthwise conv of [xin, B, C], width W)
    dt = softplus(dt_raw + dt_bias);  A = -exp(A_log)          per head
    S_t = exp(dt_t A) S_{t-1} + dt_t B_t x_t^T;  y_t = C_t S_t + D x_t
    y = rmsnorm_g(y * silu(z)) * norm   (the gated norm, per B/C group)
    x = x + y W_out

then ``rmsnorm(x) * final_norm``, and logits by the tied embedding. The
SSD is the paper's own minimal form (its Listing 1): the quadratic form
within each block of ``BLOCK`` tokens, and the block-to-block recurrence
as one decay matrix over the blocks, with no scan.

Departures of the registry model that this reference follows, so that
both compute the same function: the conv has no bias (the published
layer has one, 1,792 values a layer), and the norms' epsilon is 1e-6
(the published 1e-5). ``in_proj`` is five matrices here, one in the
published checkpoint: the same parameters, split.

Parameters are a dict with the registry's names and layout (layers
stacked on a leading axis), so that one set of weights, made by
:func:`init` from the seed, can be handed to both. The loss is
float32 next-token cross entropy under ``jax.default_matmul_precision
("highest")``; ``dtype`` bfloat16 computes the forward and backward
passes in bfloat16 instead (a control that must fail the comparison).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

BLOCK = 256          # SSD blocks of gcd(sequence length, BLOCK) tokens
EPS = 1e-6


def sizes(conf: dict) -> tuple:
    """The hashable sizes of a configuration file's model."""
    return (int(conf["n_layers"]), int(conf["d_model"]), int(conf["vocab"]),
            int(conf["ssm_heads"]), int(conf["ssm_head_dim"]),
            int(conf["ssm_groups"]), int(conf["ssm_state"]),
            int(conf["conv_width"]))


def init(key, sz: tuple) -> dict:
    """Weights from a key, float32: normal matrices scaled by fan-in,
    the embedding at 0.02, the paper's A in [1, 16] and dt in
    [0.001, 0.1] (log-uniform), D = 1, norms 1."""
    n_layers, d, vocab, h, p, g, n, w = sz
    din, conv = h * p, h * p + 2 * g * n
    ks = iter(jax.random.split(key, 10))
    nrm = lambda k, shape, fan: jax.random.normal(k, shape, jnp.float32) / math.sqrt(fan)
    a = jax.random.uniform(next(ks), (n_layers, h), jnp.float32, 1.0, 16.0)
    dt = jnp.exp(jax.random.uniform(next(ks), (n_layers, h), jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    mix = {
        "A_log": jnp.log(a),
        "D": jnp.ones((n_layers, h), jnp.float32),
        "conv": nrm(next(ks), (n_layers, w, conv), w),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),       # softplus^-1(dt)
        "norm": jnp.ones((n_layers, din), jnp.float32),
        "out_proj": nrm(next(ks), (n_layers, din, d), din),
        "w_B": nrm(next(ks), (n_layers, d, g * n), d),
        "w_C": nrm(next(ks), (n_layers, d, g * n), d),
        "w_dt": nrm(next(ks), (n_layers, d, h), d),
        "w_x": nrm(next(ks), (n_layers, d, din), d),
        "w_z": nrm(next(ks), (n_layers, d, din), d),
    }
    return {"embed": 0.02 * jax.random.normal(next(ks), (vocab, d), jnp.float32),
            "final_norm": jnp.ones((d,), jnp.float32),
            "group0": {"ln1": jnp.ones((n_layers, d), jnp.float32), "mix": mix}}


def rmsnorm(x, w):
    x32 = x.astype(jnp.float32)
    y = x32 / jnp.sqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + EPS)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def segsum(a):
    """``out[..., i, j] = sum(a[..., j+1 : i+1])`` for j <= i, -inf above."""
    t = a.shape[-1]
    c = jnp.cumsum(a, axis=-1)
    s = c[..., :, None] - c[..., None, :]
    return jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)


def ssd(x, dt, a, b, c):
    """y_t = sum_{s<=t} C_t . (prod_{s<r<=t} exp(dt_r A)) dt_s B_s x_s^T.

    x (b, s, h, p); dt (b, s, h); a (h,); b, c (b, s, h, n)."""
    bs, s, h, p = x.shape
    blk = math.gcd(s, BLOCK)
    nb = s // blk
    xd = (x * dt[..., None]).reshape(bs, nb, blk, h, p)
    ad = (dt * a).reshape(bs, nb, blk, h).transpose(0, 3, 1, 2)      # b h c l
    b = b.reshape(bs, nb, blk, h, -1)
    c = c.reshape(bs, nb, blk, h, -1)
    acum = jnp.cumsum(ad, axis=-1)
    y_diag = jnp.einsum("bclhn,bcshn,bhcls,bcshp->bclhp", c, b,
                        jnp.exp(segsum(ad)), xd)
    states = jnp.einsum("bclhn,bhcl,bclhp->bchpn", b,
                        jnp.exp(acum[..., -1:] - acum), xd)
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], axis=1)
    chunk_decay = jnp.exp(segsum(jnp.pad(acum[..., -1], ((0, 0), (0, 0), (1, 0)))))
    states = jnp.einsum("bhzc,bchpn->bzhpn", chunk_decay, states)[:, :-1]
    y_off = jnp.einsum("bclhn,bchpn,bhcl->bclhp", c, states, jnp.exp(acum))
    return (y_diag + y_off).reshape(bs, s, h, p)


def mixer(m, hid, sz, drop_d=False):
    _, _, _, h, p, g, n, w = sz
    bs, s, _ = hid.shape
    din = h * p
    xbc = jnp.concatenate([hid @ m["w_x"], hid @ m["w_B"], hid @ m["w_C"]], axis=-1)
    pad = jnp.pad(xbc, ((0, 0), (w - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(pad[:, k:k + s] * m["conv"][k] for k in range(w)))
    x = xbc[..., :din].reshape(bs, s, h, p)
    per_head = lambda t: jnp.repeat(t.reshape(bs, s, g, n), h // g, axis=2)
    b, c = per_head(xbc[..., din:din + g * n]), per_head(xbc[..., din + g * n:])
    dt = jax.nn.softplus((hid @ m["w_dt"]).astype(jnp.float32)
                         + m["dt_bias"].astype(jnp.float32))
    a = -jnp.exp(m["A_log"].astype(jnp.float32))
    y = ssd(x.astype(jnp.float32), dt, a, b.astype(jnp.float32),
            c.astype(jnp.float32)).astype(hid.dtype)
    if not drop_d:
        y = y + m["D"][:, None] * x
    gated = (y.reshape(bs, s, din) * jax.nn.silu(hid @ m["w_z"])).reshape(bs, s, g, din // g)
    y = rmsnorm(gated, m["norm"].reshape(g, din // g)).reshape(bs, s, din)
    return y @ m["out_proj"]


def loss(params, tokens, targets, sz, dtype="float32", drop_d=False):
    """Mean next-token cross entropy of a batch, float32."""
    pc = jax.tree.map(lambda v: v.astype(dtype), params)
    x = pc["embed"][tokens]

    def layer(x, lp):
        return x + mixer(lp["mix"], rmsnorm(x, lp["ln1"]), sz, drop_d), None

    x, _ = jax.lax.scan(jax.checkpoint(layer), x, pc["group0"])
    logits = (rmsnorm(x, pc["final_norm"]) @ pc["embed"].T).astype(jnp.float32)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)


@partial(jax.jit, static_argnames=("sz", "steps", "micro", "dtype", "drop_d"))
def local_update(params, tokens, targets, lr, sz, steps, micro,
                 dtype="float32", drop_d=False):
    """One client's upload ``w_0 - w_E`` after ``steps`` SGD steps on its
    batch, each on the mean gradient over ``micro`` microbatches, and the
    mean of the steps' losses. Runs at float32 ``highest`` precision."""
    rows = tokens.shape[0] // micro
    mb = lambda t: t.reshape(micro, rows, *t.shape[1:])
    grad = jax.value_and_grad(partial(loss, sz=sz, dtype=dtype, drop_d=drop_d))

    def step(w, _):
        def one(acc, batch):
            l, g = grad(w, *batch)
            return (acc[0] + l, jax.tree.map(jnp.add, acc[1], g)), None

        zero = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, w))
        (l, g), _ = jax.lax.scan(one, zero, (mb(tokens), mb(targets)))
        return jax.tree.map(lambda v, gv: v - lr * (gv / micro), w, g), l / micro

    with jax.default_matmul_precision("highest"):
        w, losses = jax.lax.scan(step, params, None, length=steps)
    return jax.tree.map(jnp.subtract, params, w), jnp.mean(losses)
