"""The trainer path on the CPU at the registry's smoke sizes of
mamba2-130m: its required work by hand, the plain references against the
program, and the check's numbers telling the control and each planted
fault from an honest run. The limits that turn those numbers into
``correct`` come from on-chip readings, which this cell has not had yet,
and until then the check refuses to decide.

Four clients need four devices, which a process fixes when it first
starts JAX, so the cases that run the mesh share one child process with
four host devices (as ``tests/test_distributed.py`` does).
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import core  # noqa: E402
from bench.paths import train as tp  # noqa: E402

# the registry's smoke model (repro.configs.mamba2_130m.smoke), and a mix
# whose sequences span three reference blocks and six program chunks
SMOKE = dict(n_layers=2, d_model=128, vocab=512, ssm_heads=4, ssm_head_dim=32,
             ssm_groups=1, ssm_state=16, ssm_chunk=8, conv_width=4)
# (alpha by the 2x2 file's recipe at these sizes, probe seed 1: -0.298)
SMOKE_MIX = dict(seq_len=48, seqs_per_client=2, microbatch=2, local_steps=2, alpha=-0.3)


def _smoke_cell():
    cell = core.make_cell("mamba2-130m", "2x2", name="train.mamba2-130m.2x2", chips=4)
    cell["config_data"] = dict(cell["config_data"], **SMOKE)
    cell["traffic_data"] = dict(cell["traffic_data"], **SMOKE_MIX)
    return cell


def test_work_is_the_hand_count():
    work = core.load_module("work", "train")
    c = dict(n_layers=1, d_model=2, vocab=3, ssm_heads=1, ssm_head_dim=2,
             ssm_groups=1, ssm_state=1, ssm_chunk=2, conv_width=2)
    mix = dict(local_steps=1, seqs_per_client=1, seq_len=2)
    # matrices: W_x, W_z 2x2 each, W_B, W_C 2x1, W_dt 2x1, out_proj 2x2,
    # head 3x2: 24 parameters. SSD a token: (2+1)/2 causal scores x (1
    # state + 2 channels), state update and read-out 2 x 1 x 2, conv 2 taps
    # x 4 channels: 16.5 products. 6 operations x 2 tokens.
    # parameters: 14 + 4 out_proj + 8 conv + 3 head scalars + 2 norm + 2 ln1,
    # embedding 6, final norm 2: 41; five float32 passes over them.
    assert work.required(c, mix) == {"ops": 6 * 2 * (24 + 16.5), "bytes": 5 * 41 * 4}


def test_work_counts_the_registry_models_parameters():
    import jax
    from repro.models.model import init_params
    cell = core.make_cell("mamba2-130m", "2x2")
    cfg = tp.arch(cell["config_data"], cell["traffic_data"])
    shapes = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.key(0))
    assert (core.load_module("work", "train").parameters(cell["config_data"])
            == sum(x.size for x in jax.tree.leaves(shapes)) == 128_940_480)


def test_reference_loss_and_gradients_match_the_model():
    """float32 on the CPU, where a matrix product rounds alike on both
    sides; the SSD is a chunk scan (chunk 8) in the program and blocks of
    16 in the reference, so they differ by rounding alone."""
    import jax
    import jax.numpy as jnp
    from repro.models import loss_fn
    from repro.models.model import init_params
    from bench.reference import mamba2_lm as lm
    cell = _smoke_cell()
    cfg = tp.arch(cell["config_data"], cell["traffic_data"])
    sz = lm.sizes(cell["config_data"])
    params = lm.init(jax.random.key(3), sz)
    shapes = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.key(0))
    assert (jax.tree.map(lambda x: (x.shape, x.dtype), params)
            == jax.tree.map(lambda x: (x.shape, x.dtype), shapes))
    batch = tp.make_batch(jax.random.key(4), 0, 2, 48, 512)
    lp, gp = jax.value_and_grad(lambda p: loss_fn(p, cfg, batch))(params)
    lr, gr = jax.value_and_grad(
        lambda p: lm.loss(p, batch["tokens"], batch["targets"], sz))(params)
    assert abs(float(lp) - float(lr)) <= 1e-6 * abs(float(lr))
    for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gr)):
        assert float(jnp.linalg.norm(a - b)) <= 1e-5 * float(jnp.linalg.norm(b))
    # the D skip is part of what is compared
    assert abs(float(lm.loss(params, batch["tokens"], batch["targets"], sz,
                             drop_d=True)) - float(lr)) > 1e-3


CHILD = r"""
import json, sys
sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src"]
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from bench.paths import train as tp
from bench.reference import fediac_allreduce as fa, fediac_round as fr
from bench.tests import test_bench_train as t
from repro.compat import make_mesh
from repro.core.fediac import fediac_allreduce

cell = t._smoke_cell()
traffic = cell["traffic_data"]
mesh = make_mesh((4, 1), ("data", "model"))
d = 3 * 4096 + 77
u = jax.random.normal(jax.random.key(1), (4, d)) ** 3
r = 0.1 * jax.random.normal(jax.random.key(2), (4, d))
key = jax.random.key(7)
cfg = tp.fediac_config(traffic)
prog = jax.jit(jax.shard_map(
    lambda u, r, k: (lambda m, nr: (m, nr[None]))(
        *fediac_allreduce(u[0], r[0], k, cfg, client_axes=("data",))),
    mesh=mesh, in_specs=(P("data"), P("data"), P()), out_specs=(P(), P("data")),
    check_vma=False))
mean_p, res_p = prog(u, r, key)
mean_r, res_r = fa.allreduce(u, jnp.array(r), key, fr.params(traffic))
bits = lambda x: np.asarray(x).view(np.uint32)
print(json.dumps({"allreduce": {
    "mean_off": int((bits(mean_p) != bits(mean_r)).sum()),
    "residual_off": int((bits(res_p) != bits(res_r)).sum()),
    "kept": int((np.asarray(mean_r) != 0).sum())}}), flush=True)

for variant in tp.VARIANTS:
    run = tp.Run(cell, 2 ** 40 + 3, jax.devices(), variant=variant)
    run.round()                       # one window round after set-up
    try:
        run.check()
        refused = False
    except RuntimeError:
        refused = True
    print(json.dumps({"variant": variant, "readings": dict(run.readings()),
                      "refused": refused}), flush=True)
"""


@pytest.fixture(scope="module")
def child():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", CHILD, str(ROOT)], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-4000:]
    lines = [json.loads(x) for x in p.stdout.splitlines() if x.startswith("{")]
    return {x.get("variant", "allreduce"): x for x in lines}


def test_reference_allreduce_is_the_programs_bit_for_bit(child):
    got = child["allreduce"]["allreduce"]
    assert got["mean_off"] == 0 and got["residual_off"] == 0
    assert got["kept"] > 0


@pytest.mark.parametrize("variant", tp.VARIANTS[1:])
def test_the_check_tells_each_fault_from_the_program(child, variant):
    """At these sizes the program and the reference round alike (float32
    on the CPU); each fault, and the control, reads at least one number
    a hundred times the honest run's."""
    honest, got = child["program"]["readings"], child[variant]["readings"]
    assert any(got[n] > 100 * max(honest[n], 1e-9) for n in got), (honest, got)


def test_the_check_refuses_without_limits(child):
    assert all(child[v]["refused"] for v in tp.VARIANTS)
    assert set(child["program"]["readings"]) == set(tp.LIMITS)


def test_collective_ms_reads_the_exchange_a_round():
    from bench import trace
    tr = {"devices": {"/device:TPU:0": [["fusion.1", "fusion", 1000, 500],
                                        ["all-reduce.3", "all-reduce", 2000, 300],
                                        ["psum.4", "all-reduce", 2400, 100]]},
          "host": [["input", 900, 100], ["round", 1000, 50], ["block", 1050, 2300]]}
    red = trace.reduce(tr, rounds=2)
    got = core.load_module("metrics", "collective_ms").read({"trace": red})
    assert got == pytest.approx(1e3 * 400e-9 / 2)
    red["collective_s"] = 0.0     # a trace with no exchange: nothing to read
    assert core.load_module("metrics", "collective_ms").read({"trace": red}) is None
