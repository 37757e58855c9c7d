"""The six kernel entry points of ``repro.kernels.ops`` compile for a TPU
v5e at real width (d = 2^24, N = 32 for the popcount), and the stream
engine's block-mode round compiles with phase 2 writing in place.

The chip is described, not attached: the TPU compiler runs on this host
and refuses what the chip's compiler would refuse (an unsigned reduction,
an unaligned slice, too much VMEM).  Nothing runs, so these tests say
nothing about results or times.  The topology is described inside a
fixture, never while a module is imported, so every test worker collects
the same tests and only the worker that runs this file loads the TPU
library.
"""

from __future__ import annotations

import os
import re
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.fediac import FediACConfig
from repro.core.stream_engine import aggregate_stream
from repro.kernels import ops

D = 2 ** 24
N_COUNT = 32
W = D // 32


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache; keep it out of the cache.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


CASES = {
    "pack_votes": (ops.pack_votes, [((D,), jnp.uint8)]),
    "unpack_votes": (partial(ops.unpack_votes, d=D), [((W,), jnp.uint32)]),
    "count_votes": (partial(ops.count_votes, d=D),
                    [((N_COUNT, W), jnp.uint32)]),
    "quantize_flat": (ops.quantize_flat,
                      [((D,), jnp.float32), ((D,), jnp.float32),
                       ((), jnp.float32)]),
    "pack_votes_threshold": (ops.pack_votes_threshold,
                             [((D,), jnp.float32), ((), jnp.float32)]),
    "gather_quant_flat": (ops.gather_quant_flat,
                          [((D,), jnp.float32), ((D,), jnp.float32),
                           ((D,), jnp.uint8), ((), jnp.float32)]),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = CASES[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(partial(fn, interpret=False)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_stream_round_phase2_writes_in_place_for_v5e(one_chip):
    """The block-mode stream round, input donated, keeps no second
    ``[N, d]`` stack: phase 2 writes the residual over the donated input
    and the delta into a flat (d,) carry.  A zeroed residual carry, or a
    copy of it into the output, shows as an ``f32[N, d]`` broadcast or
    copy in the optimized program and as an ``[N, d]`` temporary."""
    n, d = 8, 2 ** 22 + 3 * 4096   # 16 chunks and a tail
    cfg = FediACConfig(vote_mode="threshold", compact_mode="block",
                       block_size=4096, alpha=-0.2)
    fn = jax.jit(lambda u, k: aggregate_stream(u, cfg, k, chunk=2 ** 18)[:3],
                 donate_argnums=0)
    u = jax.ShapeDtypeStruct((n, d), jnp.float32, sharding=one_chip)
    k = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    compiled = fn.lower(u, k).compile()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < n * d * 4 // 4, temp
    stack_ops = re.findall(
        rf"= f32\[{n},{d}\]\{{[^}}]*\}} (copy|broadcast)\(",
        compiled.as_text())
    assert not stack_ops, stack_ops
