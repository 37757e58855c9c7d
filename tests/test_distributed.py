"""Distributed paths on emulated multi-device meshes.

Device count locks at first jax init, so these run in subprocesses with
XLA_FLAGS=--xla_force_host_platform_device_count=8.
"""

import os
import subprocess
import sys


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, devices: int = 8, timeout: int = 520):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    # pin the backend: with JAX_PLATFORMS unset, a box that carries a TPU
    # runtime stalls for minutes probing instance metadata before falling
    # back, blowing the subprocess timeout
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", code], env=env, timeout=timeout,
                       capture_output=True, text=True)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-4000:]}"
    return r.stdout


def test_fediac_allreduce_on_mesh():
    out = _run("""
import jax, jax.numpy as jnp, numpy as np
from functools import partial
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.compat import make_mesh
from repro.core.fediac import FediACConfig, fediac_allreduce
mesh = make_mesh((4, 2), ("data", "model"))
cfg = FediACConfig(k_frac=0.1, bits=12, capacity_frac=0.1)
d = 1024
u = jax.random.normal(jax.random.PRNGKey(0), (4, d)) ** 3
res = jnp.zeros((4, d))
@partial(shard_map, mesh=mesh,
         in_specs=(P("data", "model"), P("data", "model"), P()),
         out_specs=(P(None, "model"), P("data", "model")))
def step(u_l, r_l, key):
    m, r = fediac_allreduce(u_l[0], r_l[0], key, cfg, client_axes="data")
    return m[None], r[None]
mean, new_res = step(u, res, jax.random.PRNGKey(7))
recon = (u - new_res).mean(axis=0)
assert np.allclose(np.asarray(recon), np.asarray(mean[0]), atol=1e-3)
print("OK")
""")
    assert "OK" in out


def test_train_step_loss_decreases_on_mesh():
    out = _run("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding
from repro.configs import get_smoke
from repro.launch.mesh import make_test_mesh
from repro.models.model import init_params
from repro.training.dist_step import make_train_step
from repro.data.synthetic import lm_batches

cfg = get_smoke("qwen3_0p6b")
mesh = make_test_mesh()
bundle = make_train_step(cfg, mesh, lr=0.2)
with mesh:
    params = jax.jit(lambda k: init_params(cfg, k),
        out_shardings=jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s),
                                             bundle.params_spec))(jax.random.PRNGKey(0))
    residual = jax.tree_util.tree_map(
        lambda p: jnp.zeros((bundle.n_clients, *p.shape), jnp.float32), params)
    step = jax.jit(bundle.step)
    import numpy as np
    rng = np.random.default_rng(0)
    losses = []
    key = jax.random.PRNGKey(1)
    for b in lm_batches(rng, cfg.vocab, 8, 64, 8):
        batch = {k: jnp.asarray(v) for k, v in b.items()}
        key, sk = jax.random.split(key)
        params, residual, m = step(params, residual, batch, sk)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.5, losses
    print("OK", losses[0], losses[-1])
""")
    assert "OK" in out


def test_multipod_pod_mode_train_step_runs():
    out = _run("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding
from repro.configs import get_smoke
from repro.launch.mesh import make_test_mesh
from repro.models.model import init_params
from repro.training.dist_step import make_train_step
cfg = get_smoke("chameleon_34b").with_(fsdp=True)
mesh = make_test_mesh(multi_pod=True)
bundle = make_train_step(cfg, mesh, lr=0.1)
assert bundle.mode == "pod" and bundle.n_clients == 2
with mesh:
    params = jax.jit(lambda k: init_params(cfg, k),
        out_shardings=jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s),
                                             bundle.params_spec))(jax.random.PRNGKey(0))
    residual = jax.tree_util.tree_map(
        lambda p: jnp.zeros((2, *p.shape), jnp.float32), params)
    batch = {"tokens": jnp.zeros((8, 32), jnp.int32),
             "targets": jnp.zeros((8, 32), jnp.int32)}
    p2, r2, m = jax.jit(bundle.step)(params, residual, batch, jax.random.PRNGKey(1))
    assert np.isfinite(float(m["loss"]))
    # params actually moved
    delta = sum(float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).sum())
                for a, b in zip(jax.tree_util.tree_leaves(params),
                                jax.tree_util.tree_leaves(p2)))
    assert delta > 0
    print("OK")
""")
    assert "OK" in out


def test_mesh_baselines_and_packed_votes():
    out = _run("""
import jax, jax.numpy as jnp, numpy as np
from functools import partial
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.compat import make_mesh
from repro.core.fediac import FediACConfig, fediac_allreduce
from repro.core.mesh_baselines import switchml_allreduce, topk_allreduce
mesh = make_mesh((4, 2), ("data", "model"))
d = 262144
u = jax.random.normal(jax.random.PRNGKey(0), (4, d)) ** 3
res = jnp.zeros((4, d))
def run(fn, cfg):
    @partial(shard_map, mesh=mesh,
             in_specs=(P("data", "model"), P("data", "model"), P()),
             out_specs=(P(None, "model"), P("data", "model")), check_vma=False)
    def step(u_l, r_l, key):
        m, r = fn(u_l[0], r_l[0], key, cfg, client_axes="data")
        return m[None], r[None]
    return step(u, res, jax.random.PRNGKey(7))
# topk + packed-vote fediac conserve mass (error feedback identity)
for fn, cfg in [(topk_allreduce, FediACConfig(k_frac=0.05)),
                (fediac_allreduce, FediACConfig(vote_wire="packed"))]:
    mean, new_res = run(fn, cfg)
    recon = (u - new_res).mean(axis=0)
    assert np.allclose(np.asarray(recon), np.asarray(mean[0]), atol=2e-2)
# switchml: unbiased dense (no EF): mean ~= u.mean within quant step
mean, _ = run(switchml_allreduce, FediACConfig(bits=14))
err = float(jnp.abs(mean[0] - u.mean(0)).max())
assert err < float(jnp.abs(u).max()) / 2**10, err
print("OK")
""")
    assert "OK" in out


def test_dryrun_smoke_single_combo():
    """The dry-run module itself (512 fake devices) on a reduced config."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["JAX_PLATFORMS"] = "cpu"  # see _run: avoid the TPU-probe stall
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "mamba2-130m",
         "--shape", "decode_32k", "--smoke", "--out",
         os.path.join(REPO, "benchmarks", "results", "dryrun_test")],
        env=env, timeout=520, capture_output=True, text=True, cwd=REPO)
    assert r.returncode == 0, r.stdout + r.stderr[-2000:]
    assert "FAIL" not in r.stdout
