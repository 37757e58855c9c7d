"""Training launcher.

Builds a mesh over every visible device (or the production mesh) and runs
the distributed FL step: on one chip a single client, on a 2x2 host two
clients (``data``) with model parallelism 2 (``model``).  On the CPU, use
XLA_FLAGS=--xla_force_host_platform_device_count=N to emulate a mesh.

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b --smoke \
      --steps 20 --batch 8 --seq 128
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from repro.checkpoint import save_checkpoint
from repro.configs import get, get_smoke
from repro.data.synthetic import lm_batches
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_production_mesh, make_test_mesh
from repro.models.model import init_params
from repro.training.dist_step import make_train_step


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--production-mesh", action="store_true",
                    help="16x16 (needs 256 devices)")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--aggregator", default=None, choices=[None, "fediac", "dense"])
    ap.add_argument("--ckpt", default=None)
    return ap.parse_args(argv)


def _sumsq(tree) -> float:
    return float(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                     for x in jax.tree_util.tree_leaves(tree)))


def train(args) -> dict:
    """Run ``args.steps`` distributed FL steps; returns the per-step
    ``losses`` and ``update_norms`` and the parameters' sum of squares
    before and after (``param_sumsq``), which shows that they moved."""
    cfg = get_smoke(args.arch) if args.smoke else get(args.arch)
    if args.aggregator:
        cfg = cfg.with_(aggregator=args.aggregator)
    mesh = (make_production_mesh(multi_pod=args.multi_pod)
            if args.production_mesh else make_test_mesh(multi_pod=args.multi_pod))

    bundle = make_train_step(cfg, mesh, lr=args.lr)
    key = jax.random.PRNGKey(0)
    shard = lambda spec: jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec)
    losses, norms = [], []
    with mesh:
        params = jax.jit(lambda k: init_params(cfg, k),
                         out_shardings=shard(bundle.params_spec))(key)
        if bundle.mode == "plain":
            residual = jnp.zeros((), jnp.float32)
        else:
            # built in place with the residual's sharding: an unsharded
            # [clients, ...] stack would land whole on the first device.
            residual = jax.jit(
                lambda p: jax.tree_util.tree_map(
                    lambda x: jnp.zeros((bundle.n_clients, *x.shape),
                                        jnp.dtype(cfg.residual_dtype)), p),
                out_shardings=shard(bundle.residual_spec))(params)
        sumsq0 = _sumsq(params)
        # params and residual are replaced every step: donating them lets
        # the step write the new state over the old.
        step = jax.jit(bundle.step, donate_argnums=(0, 1))

        rng = np.random.default_rng(0)
        t0 = time.time()
        for i, b in enumerate(lm_batches(rng, cfg.vocab, args.batch, args.seq,
                                         args.steps)):
            if cfg.is_enc_dec:
                b["frames"] = np.asarray(
                    rng.normal(size=(args.batch, cfg.source_len, cfg.d_model)),
                    np.float32) * 0.02
            batch = {k: jnp.asarray(v) for k, v in b.items()}
            key, sk = jax.random.split(key)
            params, residual, metrics = step(params, residual, batch, sk)
            losses.append(float(metrics["loss"]))
            norms.append(float(metrics["update_norm"]))
            print(f"step {i:4d} loss={losses[-1]:.4f} |u|={norms[-1]:.4f} "
                  f"({time.time() - t0:.1f}s)")
        sumsq1 = _sumsq(params)
    if args.ckpt:
        save_checkpoint(args.ckpt, jax.device_get(params), step=args.steps)
        print(f"saved checkpoint to {args.ckpt}")
    return {"losses": losses, "update_norms": norms,
            "param_sumsq": (sumsq0, sumsq1)}


def main(argv=None):
    enable_compile_cache()
    train(parse_args(argv))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
