"""The streaming chunked round engine (DESIGN.md §12): chunked-vs-monolithic
bit-identity across every vote x compact mode pair, sliceable random
streams, the packet-transport path, engine selection, and buffer donation.

The load-bearing contract: ``aggregate_stream`` output (delta, residuals,
vote counts, traffic bytes) equals ``aggregate_stack`` **bitwise** for any
chunk size — including chunk sizes that do not divide d — because phase-1
integer count sums are associative, the consensus threshold + tie-break
rule is shared (``build_round_plan``), and chunks cover disjoint index
ranges.
"""

from __future__ import annotations

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.fediac import (FediACConfig, aggregate_round, aggregate_stack,
                               build_round_plan, phase2_compress,
                               plan_wants_dense_mask, _vote_counts_stack)
from repro.core.quantize import scale_factor
from repro.core.stream_engine import aggregate_stream, stream_compress_stack
from repro.core.streams import uniform_block

KEY = jax.random.PRNGKey(7)

MODES = [("topk", "topk"), ("topk", "block"),
         ("threshold", "topk"), ("threshold", "block")]


def _u(n, d, seed=1):
    return jax.random.normal(jax.random.PRNGKey(seed), (n, d)) ** 3


def _assert_rounds_equal(a, b):
    """(delta, residuals, counts, traffic) bitwise + byte accounting."""
    for name, x, y in zip(("delta", "residuals", "counts"), a[:3], b[:3]):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y), err_msg=name)
    assert a[3] == b[3], "traffic stats"


# ---------------------------------------------------------------------------
# sliceable random streams
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("partitionable", [False, True])
@pytest.mark.parametrize("d", [17, 1000, 1001, 65536])
def test_uniform_block_matches_monolithic_draw(partitionable, d):
    """Chunk slices of the reconstructed stream == slices of the one-shot
    draw, under both threefry layouts (the engine must be exact whichever
    the host config selects)."""
    was = jax.config.jax_threefry_partitionable
    jax.config.update("jax_threefry_partitionable", partitionable)
    try:
        key = jax.random.PRNGKey(42)
        ref = np.asarray(jax.random.uniform(key, (d,)))
        full = np.asarray(uniform_block(key, 0, d, d))
        np.testing.assert_array_equal(full, ref)
        s, size = d // 3, d // 2
        part = np.asarray(uniform_block(key, s, size, d))
        np.testing.assert_array_equal(part, ref[s:s + size])
    finally:
        jax.config.update("jax_threefry_partitionable", was)


def test_uniform_block_traced_start():
    key = jax.random.PRNGKey(3)
    ref = np.asarray(jax.random.uniform(key, (999,)))
    sl = jax.jit(lambda s: uniform_block(key, s, 100, 999))
    np.testing.assert_array_equal(np.asarray(sl(jnp.int32(123))),
                                  ref[123:223])


# ---------------------------------------------------------------------------
# chunked-vs-monolithic bit-identity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("vote_mode,compact_mode", MODES)
@pytest.mark.parametrize("n", [1, 8])
def test_stream_bit_identical(vote_mode, compact_mode, n):
    """All four mode pairs, N in {1, 8}, d NOT divisible by the chunk."""
    cfg = FediACConfig(vote_mode=vote_mode, compact_mode=compact_mode,
                       block_size=256)
    u = _u(n, 10_000)
    _assert_rounds_equal(aggregate_stack(u, cfg, KEY),
                         aggregate_stream(u, cfg, KEY, chunk=1536))


@pytest.mark.parametrize("chunk", [512, 4096, 9999, 100_000])
def test_stream_chunk_size_invariant(chunk):
    """Any chunk size — smaller, non-dividing, larger than d — same bits."""
    cfg = FediACConfig()
    u = _u(6, 9999)
    _assert_rounds_equal(aggregate_stack(u, cfg, KEY),
                         aggregate_stream(u, cfg, KEY, chunk=chunk))


def test_stream_bit_identical_on_fast_path():
    """d above the selection fast-path gate (the certificate machinery runs
    inside the client scan) with boundary ties."""
    cfg = FediACConfig()
    u = jnp.round(_u(4, 300_000) * 4) / 4
    a = jax.jit(lambda u, k: aggregate_stack(u, cfg, k)[:3])(u, KEY)
    b = jax.jit(lambda u, k: aggregate_stream(u, cfg, k)[:3])(u, KEY)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_stream_fused_pallas_path():
    """use_pallas routes per-chunk gather_quant kernel calls; the d-sized
    uniform stream is sliced, not re-drawn — bitwise equal to the
    monolithic fused path."""
    cfg = FediACConfig(use_pallas=True)
    u = _u(6, 10_000)
    _assert_rounds_equal(aggregate_stack(u, cfg, KEY),
                         aggregate_stream(u, cfg, KEY, chunk=1536))


def test_stream_traced_threshold_override():
    """The sweep engine's traced vote-threshold scalar batches through the
    streaming engine exactly as through the monolithic one."""
    cfg = FediACConfig()
    u = _u(5, 4096)
    static = aggregate_stream(u, cfg, KEY, a=2, chunk=1000)
    traced = jax.jit(
        lambda u, k, a: aggregate_stream(u, cfg, k, a=a, chunk=1000)[:3])(
            u, KEY, jnp.int32(2))
    for x, y in zip(static[:3], traced):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_stream_rejects_chunked_votes():
    with pytest.raises(NotImplementedError):
        aggregate_stream(_u(2, 64), FediACConfig(vote_chunk=4), KEY)


def test_aggregate_round_dispatch():
    u = _u(4, 4096)
    mono = aggregate_round(u, FediACConfig(), KEY)
    stream = aggregate_round(u, FediACConfig(engine="stream",
                                             stream_chunk=1000), KEY)
    _assert_rounds_equal(mono, stream)
    with pytest.raises(ValueError):
        aggregate_round(u, FediACConfig(engine="nope"), KEY)


# ---------------------------------------------------------------------------
# per-client compress (the packet-dataplane half)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("vote_mode,compact_mode,use_pallas",
                         [("topk", "topk", False), ("topk", "block", False),
                          ("threshold", "topk", False),
                          ("threshold", "block", False),
                          ("topk", "topk", True)])
def test_stream_compress_stack_matches_vmap(vote_mode, compact_mode,
                                            use_pallas):
    cfg = FediACConfig(vote_mode=vote_mode, compact_mode=compact_mode,
                       use_pallas=use_pallas, block_size=256)
    u = _u(6, 10_000)
    n, d = u.shape
    keys = jax.random.split(KEY, 2 * n)
    counts = _vote_counts_stack(u, cfg, keys[:n])
    f = scale_factor(cfg.bits, n, 1.0) / jnp.clip(jnp.max(jnp.abs(u)),
                                                  1e-12, None)
    topk = cfg.compact_mode != "block"
    plan = build_round_plan(counts, cfg, n,
                            with_dense_mask=topk or plan_wants_dense_mask(cfg),
                            with_slot_map=topk)
    compress = phase2_compress(cfg)
    qb_ref, res_ref = jax.vmap(
        lambda uu, kk: compress(uu, cfg, f, kk, plan))(u, keys[n:])
    qb, res = stream_compress_stack(u, cfg, f, keys[n:], plan, chunk=1536)
    np.testing.assert_array_equal(np.asarray(qb), np.asarray(qb_ref))
    np.testing.assert_array_equal(np.asarray(res), np.asarray(res_ref))


@pytest.mark.parametrize("vote_mode,compact_mode", MODES)
def test_packet_transport_drives_streaming_engine(vote_mode, compact_mode):
    """The lossless full-participation packet round (register windows,
    hierarchy drain) stays bit-identical to ``aggregate_stack`` when the
    phase-2 compress streams through chunks."""
    from repro.netsim import NetConfig, PacketTransport
    cfg = FediACConfig(vote_mode=vote_mode, compact_mode=compact_mode,
                       block_size=256, engine="stream", stream_chunk=1000)
    u = _u(5, 6000)
    delta0, res0, counts0, traffic0 = aggregate_stack(
        u, FediACConfig(vote_mode=vote_mode, compact_mode=compact_mode,
                        block_size=256), KEY)
    tp = PacketTransport("fediac", {"cfg": cfg}, net=NetConfig())
    out = tp.round(u, None, KEY)
    np.testing.assert_array_equal(np.asarray(out.delta), np.asarray(delta0))
    np.testing.assert_array_equal(np.asarray(out.residuals), np.asarray(res0))
    np.testing.assert_array_equal(np.asarray(out.stats["vote_counts"]),
                                  np.asarray(counts0))


# ---------------------------------------------------------------------------
# engine selection through the FL loop and the fleet
# ---------------------------------------------------------------------------

def test_fl_loop_engine_override_bit_identical():
    """FLConfig(engine='stream') must not change a single training bit."""
    from repro.data import classification, partition_iid
    from repro.training.fl_loop import FLConfig, run_federated
    data = classification(n=400, dim=12, n_classes=5, seed=0)
    train, test = data.test_split(0.25)
    clients = partition_iid(train, 4, 0)
    base = dict(n_clients=4, rounds=2, local_steps=2, batch=8, seed=0,
                agg_kwargs={"cfg": FediACConfig(stream_chunk=100)})
    h_mono = run_federated(clients, test, FLConfig(**base), hidden=(16,))
    h_stream = run_federated(clients, test,
                             FLConfig(engine="stream", **base), hidden=(16,))
    assert h_mono.acc == h_stream.acc
    assert h_mono.loss == h_stream.loss
    assert h_mono.traffic_mb == h_stream.traffic_mb


def test_fleet_runs_streaming_engine():
    """A streaming-engine scenario rides the vmapped fleet program and
    stays bit-identical to its sequential run."""
    from repro.sweep import ScenarioSpec, run_cell_sequential, run_sweep
    spec = ScenarioSpec(name="stream", algorithm="fediac", a=2,
                        engine="stream", n_clients=4, rounds=2,
                        local_steps=2, batch=8, hidden=(16,), data_n=500,
                        data_dim=12, data_classes=5)
    (cell,) = run_sweep([spec], (0,))
    h_seq = run_cell_sequential(spec, 0)
    assert cell.history.acc == h_seq.acc
    assert cell.history.traffic_mb == h_seq.traffic_mb


# ---------------------------------------------------------------------------
# buffer donation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("vote_mode,compact_mode", MODES)
def test_aggregate_stream_donates_input_stack(vote_mode, compact_mode):
    """Under jit(donate_argnums=(0,)) the u_stack buffer is consumed —
    no copy-on-donate warning fires (donation is actually usable) — and
    the outputs equal the undonated call's and the monolithic engine's
    bit for bit.  d = 5000 is four 1024-wide chunks plus a tail, so the
    block path's in-place residual and flat delta writes cover the scan
    steps and the trailing call."""
    cfg = FediACConfig(vote_mode=vote_mode, compact_mode=compact_mode,
                       block_size=256)
    u = _u(4, 5000)
    mono = aggregate_stack(u, cfg, KEY)
    ref = aggregate_stream(u, cfg, KEY, chunk=1024)
    fn = jax.jit(lambda u, k: aggregate_stream(u, cfg, k, chunk=1024)[:3],
                 donate_argnums=(0,))
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        out = jax.block_until_ready(fn(u, KEY))
    assert not [w for w in caught if "donat" in str(w.message).lower()]
    assert u.is_deleted()
    for name, x, y, z in zip(("delta", "residuals", "counts"), ref[:3], out,
                             mono[:3]):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=name)
        np.testing.assert_array_equal(np.asarray(z), np.asarray(y),
                                      err_msg=name)


def test_fl_loop_carry_in_donates():
    from repro.training.fl_loop import _carry_in
    u = jnp.ones((4, 256))
    e = jnp.full((4, 256), 2.0)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        out = jax.block_until_ready(_carry_in(u, e))
    assert not [w for w in caught if "donat" in str(w.message).lower()]
    assert u.is_deleted() and not e.is_deleted()
    np.testing.assert_array_equal(np.asarray(out), np.full((4, 256), 3.0))


def test_fleet_step_donates_round_state():
    """The fleet round program consumes (params, residuals, agg state,
    keys): donated buffers are deleted after the call and XLA raises no
    copy-on-donate warning — the K*N*d residual stack is reused in place."""
    from repro.sweep import ScenarioSpec, run_sweep
    spec = ScenarioSpec(name="donate", algorithm="fediac", a=2, n_clients=4,
                        rounds=2, local_steps=2, batch=8, hidden=(16,),
                        data_n=500, data_dim=12, data_classes=5)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        run_sweep([spec], (0,))
    assert not [w for w in caught if "donat" in str(w.message).lower()]
