"""FediAC: the paper's two-phase consensus-compressed aggregation.

Two entry points:

* :func:`aggregate_stack` — pure functional reference over a stacked
  ``[N, d]`` client-update matrix.  This is Algo. 1 verbatim and is what the
  FL simulator (``repro.training.fl_loop``) and the tests/benchmarks use.

* :func:`fediac_allreduce` — the production form: called *inside* a
  ``shard_map`` where the caller's mesh axis (or axes) enumerate clients.
  Phase 1 is a ``psum`` of uint8 vote arrays (the PS summing 0/1 arrays);
  phase 2 is a ``psum`` of an int32 *consensus-compacted* buffer of
  ``C << d`` entries.  Both psums are integer adds — the in-network
  aggregation semantics of the switch, executed hop-by-hop by the ICI ring.

Both entry points run the **round-plan engine** (DESIGN.md §3): the
consensus selection is computed exactly once per round from the shared
vote counts (:func:`repro.core.round_plan.build_round_plan`) and the
resulting plan is passed into every client's compress step — never
recomputed inside the per-client vmap.  All d-sized selections go through
:mod:`repro.core.selection` (single small sort instead of k-sized partial
sorts) and remain bit-identical to the seed formulation, which is kept
alive in :mod:`repro.core.seed_ref` as the regression oracle.

Multi-pod: pass ``client_axes=("pod", "data")``; XLA lowers the psum
hierarchically (intra-pod reduce, inter-pod exchange) which is exactly the
paper's future-work "multiple collaborative PSes" topology — each pod's
reduction stage is one PS.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Sequence

import jax
import jax.numpy as jnp

from repro.obs.scopes import scope
from repro.validate import (check_at_least, check_choice, check_interval,
                            require)

from . import compaction, robust_agg, voting
from .quantize import dequantize, quantize, scale_factor
from .round_plan import RoundPlan, build_round_plan

__all__ = ["FediACConfig", "TrafficStats", "aggregate_stack",
           "aggregate_round", "fediac_allreduce",
           "dense_allreduce", "client_compress", "client_vote_stack",
           "phase2_compress", "plan_wants_dense_mask", "scatter_sum",
           "round_traffic", "RoundPlan", "build_round_plan"]


@dataclass(frozen=True)
class FediACConfig:
    """Hyper-parameters of FediAC (paper Sec. IV / V-A3)."""

    k_frac: float = 0.05          # vote budget k = k_frac * d   (paper: 5% d)
    a: int | None = None          # vote threshold; None -> ceil(a_frac * N)
    a_frac: float = 0.15          # paper Fig. 4: a in [5%N, 20%N] is robust
    bits: int = 12                # quantization bits b (Cor. 1 lower-bounds it)
    capacity_frac: float = 0.05   # compact buffer C = capacity_frac * d
    vote_chunk: int = 1           # g coords per vote bit (1 = paper-faithful)
    vote_dtype: str = "uint8"     # wire dtype of the phase-1 psum
    vote_wire: str = "count"      # count: uint8 psum (~2d ring bytes);
                                  # packed: bit-packed all-gather + popcount
                                  # (N*d/8 bytes — wins for few clients,
                                  # e.g. 4x at N=2 pods)
    use_pallas: bool = False      # route the client round through the fused
                                  # Pallas kernels (gather_quant/vote_pack,
                                  # DESIGN.md §3).  As an *engine selector*
                                  # this field is deprecated — prefer
                                  # EngineSpec(use_pallas=True); the low-
                                  # level compress paths still read it.
    # sort-free mode for billion-parameter vectors (DESIGN.md §2): threshold
    # voting from the Def.1 power-law fit + cumsum block compaction.  The
    # exact top-k machinery needs O(d log d) sorts with ~20 GiB of workspace
    # at d ~ 1e9.
    vote_mode: str = "topk"       # topk (paper-faithful) | threshold
    compact_mode: str = "topk"    # topk (global top-C)   | block
    block_size: int = 4096        # block compaction granule
    alpha: float = -1.0           # Def.1 power-law exponent (server-fitted)
    work_dtype: str = "float32"   # dtype of the d-sized working tensors;
                                  # bfloat16 halves them for 1e9-coord shards
                                  # (quantization math stays f32 on the
                                  # compacted buffer)
    granularity: str = "model"    # model: one vote over the whole raveled
                                  # shard (paper-faithful); tensor: per-leaf
                                  # aggregation — peak memory follows the
                                  # largest tensor instead of the full shard
    # engine selection for the stacked round (DESIGN.md §12, §16): a
    # registered name or an engines.EngineSpec.  monolithic materializes
    # [N, d] temporaries; stream runs the round as a chunk scan with
    # O(N*chunk) peak memory; sharded splits the coordinate axis over a
    # device mesh — all bit-identical.  Tuning knobs (stream chunk, mesh
    # size/axis, pallas fusion) live on the EngineSpec.
    engine: "str | EngineSpec" = "monolithic"  # monolithic | stream | sharded
    stream_chunk: int = 0         # DEPRECATED: use EngineSpec(chunk=...);
                                  # still forwards (engines.resolve warns
                                  # once).  0 = engine default.
    # graceful degradation (DESIGN.md §14): when fewer than consensus_floor
    # coordinates survive the vote threshold (bursty loss / crashed voters
    # starved the GIA), fall back to the dense mask a = 1 for the round
    # instead of aggregating a near-empty consensus set.  0 disables the
    # fallback; applied once per round inside build_round_plan, so every
    # engine (monolithic, stream, packet, allreduce) inherits it.
    consensus_floor: int = 0
    # Byzantine-robust slot aggregation (DESIGN.md §18): how the client
    # axis closes within each consensus slot.  "sum" is the paper's plain
    # integer addition (every call site Python-gates on it — the sum
    # program is unchanged, not merely equal); "trim" drops the
    # floor(trim_frac * n) smallest and largest live values per slot;
    # "median" is the maximal trim.  All engines aggregate through the
    # core.robust_agg.client_sum seam; the allreduce wire path requires
    # "sum" (a psum cannot compute order statistics in-network).
    robust_agg: str = "sum"
    trim_frac: float = 0.0

    def __post_init__(self):
        check_interval("k_frac", self.k_frac, 0.0, 1.0, lo_open=True)
        check_interval("capacity_frac", self.capacity_frac, 0.0, 1.0,
                       lo_open=True)
        check_interval("a_frac", self.a_frac, 0.0, 1.0, lo_open=True)
        if self.a is not None:
            check_at_least("a", self.a, 1)
        check_at_least("bits", self.bits, 1)
        check_at_least("vote_chunk", self.vote_chunk, 1)
        check_at_least("block_size", self.block_size, 1)
        check_at_least("stream_chunk", self.stream_chunk, 0)
        check_at_least("consensus_floor", self.consensus_floor, 0)
        require(math.isfinite(self.alpha), "alpha", "finite", self.alpha)
        check_choice("vote_mode", self.vote_mode, ("topk", "threshold"))
        check_choice("compact_mode", self.compact_mode, ("topk", "block"))
        check_choice("vote_wire", self.vote_wire, ("count", "packed"))
        check_choice("granularity", self.granularity, ("model", "tensor"))
        check_choice("robust_agg", self.robust_agg, robust_agg.ROBUST_AGG_MODES)
        check_interval("trim_frac", self.trim_frac, 0.0, 0.5, hi_open=True)
        from . import engines
        engines.get(self.engine)   # registered name or EngineSpec

    def k(self, d: int) -> int:
        return max(1, int(round(self.k_frac * d)))

    def threshold(self, n_clients: int) -> int:
        """Resolved vote threshold a for an N-client round."""
        if self.a is not None:
            return max(1, min(int(self.a), n_clients))
        return max(1, min(n_clients, math.ceil(self.a_frac * n_clients)))

    def capacity(self, d: int) -> int:
        c = max(1, int(round(self.capacity_frac * d)))
        return min(c, d)


@dataclass(frozen=True)
class TrafficStats:
    """Static per-round, per-client wire accounting (bytes)."""

    phase1_bytes: int     # vote array upload (per client)
    phase2_bytes: int     # compacted quantized values upload (per client)
    dense_bytes: int      # what dense fp32 FedAvg would have uploaded
    selected: int         # compact capacity C (upper bound on #selected)

    @property
    def total_bytes(self) -> int:
        return self.phase1_bytes + self.phase2_bytes

    @property
    def reduction(self) -> float:
        return 1.0 - self.total_bytes / max(self.dense_bytes, 1)


def round_traffic(cfg: FediACConfig, d: int) -> TrafficStats:
    n_chunks = d // cfg.vote_chunk
    vote_bytes = n_chunks * jnp.dtype(cfg.vote_dtype).itemsize
    # paper wire format is 1 bit per (chunk of) coordinate; the uint8 psum is
    # the TPU realization — report the packed-bit figure too via ceil(/8).
    c = cfg.capacity(n_chunks) * cfg.vote_chunk
    phase2 = c * max(1, math.ceil(cfg.bits / 8))
    return TrafficStats(phase1_bytes=int(vote_bytes), phase2_bytes=int(phase2),
                        dense_bytes=4 * d, selected=int(c))


# ---------------------------------------------------------------------------
# Client-local compression pieces (shared by both entry points)
# ---------------------------------------------------------------------------

def _vote_scores(u: jax.Array, cfg: FediACConfig) -> jax.Array:
    """What each client ranks in phase 1 (per chunk if vote_chunk > 1)."""
    if cfg.vote_chunk > 1:
        return voting.chunk_scores(u, cfg.vote_chunk)
    return u


def _vote_scores_stack(u_stack: jax.Array, cfg: FediACConfig) -> jax.Array:
    """Stacked vote scores; at vote_chunk == 1 the per-row score map is the
    identity, and skipping the vmap saves XLA an [N, d] copy on the hot
    path (the threshold/block cells' few-percent engine regression)."""
    if cfg.vote_chunk == 1:
        return u_stack
    return jax.vmap(lambda u: _vote_scores(u, cfg))(u_stack)


def _client_votes(u: jax.Array, cfg: FediACConfig, key: jax.Array) -> jax.Array:
    """Phase-1 client side: 0/1 vote array (per chunk if vote_chunk > 1)."""
    scores = _vote_scores(u, cfg)
    k = cfg.k(scores.shape[-1])
    if cfg.vote_mode == "threshold":
        m = jnp.max(jnp.abs(scores))
        return voting.threshold_vote_mask(scores, k, m, cfg.alpha)
    return voting.vote_mask(scores, k, key)


def _vote_counts_stack(u_stack: jax.Array, cfg: FediACConfig,
                       keys: jax.Array) -> jax.Array:
    """Phase 1 over all clients at once: int32 vote counts, bit-identical
    to summing per-client vote arrays.  In topk mode the counts accumulate
    without materializing the [N, d] vote arrays and the selection
    certificate stays at batch level; the threshold branch is a plain
    vmapped indicator (already one cheap pass) summed as the seed did."""
    if cfg.vote_mode == "threshold":
        return client_vote_stack(u_stack, cfg, keys).astype(jnp.int32).sum(axis=0)
    scores = _vote_scores_stack(u_stack, cfg)
    return voting.vote_counts_stack(scores, cfg.k(scores.shape[-1]), keys)


def client_vote_stack(u_stack: jax.Array, cfg: FediACConfig,
                      vote_keys: jax.Array) -> jax.Array:
    """Per-client phase-1 vote arrays, uint8[N, d/g].

    The packet dataplane (``repro.netsim``) emits each client's votes as
    individual packets, so it needs the stacked arrays — not just their
    sum.  Summing the rows is bit-identical to :func:`_vote_counts_stack`
    (``voting.vote_counts_stack`` is the same selection computed without
    materializing the stack), which is what keeps the lossless packet
    round exactly equal to :func:`aggregate_stack`.
    """
    scores = _vote_scores_stack(u_stack, cfg)
    k = cfg.k(scores.shape[-1])
    if cfg.vote_mode == "threshold":
        return jax.vmap(
            lambda s: voting.threshold_vote_mask(s, k, jnp.max(jnp.abs(s)),
                                                 cfg.alpha))(scores)
    return voting.vote_mask_stack(scores, k, vote_keys)


def _block_compress(u: jax.Array, cfg: FediACConfig, f: jax.Array,
                    key: jax.Array, plan: RoundPlan):
    """Sort-free phase 2: cumsum block compaction (compact_mode='block').

    The block selection lives in the shared round plan; per-client work is
    one fused quantize/compact/residual pass.  This wire form (the
    ``nb*cb`` compact buffer) is what the allreduce psum and the packet
    dataplane transmit; the stacked engine uses :func:`_block_compress_dense`
    instead — the buffers would only be summed and scattered right back.
    """
    q, residual = _block_compress_dense(u, cfg, f, key, plan)
    q_buf = compaction.block_compact(q, plan.keep_dense, plan.pos,
                                     cfg.block_size, cfg.capacity_frac)
    return q_buf, residual


def _block_compress_dense(u: jax.Array, cfg: FediACConfig, f: jax.Array,
                          key: jax.Array, plan: RoundPlan):
    """Block-mode phase 2 without the wire form: (dense q int32[d],
    residual).  ``aggregate_stack`` only ever *sums* the compact buffers
    and de-compacts the sum, and ``block_scatter(sum_i block_compact(q_i))
    == where(keep, sum_i q_i, 0)`` coordinate-for-coordinate (each kept
    coordinate owns exactly one buffer slot), so the per-client
    compact/scatter round-trip — a d-sized scatter per client — is pure
    wire bookkeeping the in-memory engine can skip."""
    keep = plan.keep_dense
    uniforms = jax.random.uniform(key, u.shape, jnp.float32)
    q = quantize(jnp.where(keep, u, 0.0), f, uniforms)
    residual = (u - jnp.where(keep, dequantize(q, f), 0.0)).astype(u.dtype)
    return q, residual


def client_compress(u: jax.Array, cfg: FediACConfig, f: jax.Array,
                    key: jax.Array, plan: RoundPlan):
    """Phase-2 client side against the shared consensus round plan.

    Returns ``(q_buf int32[Cg], residual)``: the compacted quantized upload
    and the new error-feedback state.  The residual update is a fused
    scatter-subtract at the C consensus coordinates — no d-sized zeros
    buffer, bit-identical to ``u - scatter(dequantized)``.
    """
    idx_c, keep_c = plan.idx, plan.keep
    capacity = idx_c.shape[0]
    if cfg.vote_chunk > 1:
        # gather whole chunks: buffer is [C, g] flattened.
        u2 = u.reshape(-1, cfg.vote_chunk)
        gathered = jnp.take(u2, idx_c, axis=0).astype(jnp.float32) * keep_c[:, None]
        gathered = gathered.reshape(-1)
    else:
        gathered = compaction.compact(u, idx_c, keep_c).astype(jnp.float32)
    uniforms = jax.random.uniform(key, gathered.shape, jnp.float32)
    if cfg.use_pallas:
        from repro.kernels import ops as kops
        q_buf = kops.quantize_flat(gathered, uniforms, f)
    else:
        q_buf = quantize(gathered, f, uniforms)
    # own uploaded contribution, de-quantized and subtracted in place at the
    # consensus coordinates (in u's working dtype).
    up = dequantize(q_buf, f).astype(u.dtype)
    if cfg.vote_chunk > 1:
        vals = up.reshape(capacity, cfg.vote_chunk) * keep_c[:, None].astype(u.dtype)
        residual = u2.at[idx_c].add(-vals).reshape(u.shape).astype(u.dtype)
    else:
        vals = (up.astype(jnp.float32) * keep_c).astype(u.dtype)
        residual = u.at[idx_c].add(-vals).astype(u.dtype)
    return q_buf, residual


def _client_compress_fused(u: jax.Array, cfg: FediACConfig, f: jax.Array,
                           key: jax.Array, plan: RoundPlan):
    """Pallas phase 2: one ``gather_quant`` pass over u computes the masked
    stochastic quantization *and* the residual (DESIGN.md §3); the C-sized
    consensus gather then reads the already-quantized dense buffer.

    Draws d uniforms (one per coordinate) instead of the jnp path's C — the
    kernel is bit-identical to ``ref.gather_quant_ref``, statistically
    identical to (but a different random stream than) the jnp path.
    """
    from repro.kernels import ops as kops
    uniforms = jax.random.uniform(key, u.shape, jnp.float32)
    q_dense, residual = kops.gather_quant_flat(u, uniforms, plan.sel, f)
    q_buf = jnp.take(q_dense, plan.idx)
    return q_buf, residual.astype(u.dtype)


def phase2_compress(cfg: FediACConfig):
    """Pick the per-client phase-2 implementation for this config."""
    if cfg.compact_mode == "block":
        return _block_compress
    if cfg.use_pallas and cfg.vote_chunk == 1:
        return _client_compress_fused
    return client_compress


def plan_wants_dense_mask(cfg: FediACConfig) -> bool:
    return (cfg.use_pallas and cfg.vote_chunk == 1
            and cfg.compact_mode != "block")


def scatter_sum(summed_q: jax.Array, idx_c: jax.Array, keep_c: jax.Array,
                 cfg: FediACConfig, d: int) -> jax.Array:
    """De-compact the aggregated int32 buffer back to a d-vector (still ints)."""
    n_chunks = d // cfg.vote_chunk
    capacity = idx_c.shape[0]
    if cfg.vote_chunk > 1:
        out = jnp.zeros((n_chunks, cfg.vote_chunk), summed_q.dtype)
        vals = summed_q.reshape(capacity, cfg.vote_chunk) * keep_c[:, None].astype(summed_q.dtype)
        return out.at[idx_c].set(vals).reshape(-1)
    return compaction.scatter_compact(summed_q, idx_c, keep_c.astype(jnp.float32), d)


# ---------------------------------------------------------------------------
# Reference: stacked [N, d] aggregation (Algo. 1, the FL-simulator path)
# ---------------------------------------------------------------------------

def aggregate_stack(u_stack: jax.Array, cfg: FediACConfig, key: jax.Array,
                    *, a=None):
    """Run one FediAC round over N stacked client updates.

    u_stack: float32[N, d] — U_t^i = local update + carried residual.
    Returns (delta[d] — the *mean* update to apply to the global model,
             residuals[N, d], counts[d//g], TrafficStats).

    ``a`` optionally overrides the vote threshold (may be a traced int32
    scalar — the sweep engine batches threshold sweeps through one
    compiled program; see :func:`repro.core.round_plan.build_round_plan`).
    """
    n, d = u_stack.shape
    with scope("vote"):
        keys = jax.random.split(key, 2 * n)
        vote_keys, q_keys = keys[:n], keys[n:]
        # Phase 1: every client votes; the PS sums 0/1 arrays.
        counts = _vote_counts_stack(u_stack, cfg, vote_keys)
        # the global max magnitude, for the scale factor (SwitchML-style)
        m = jnp.max(jnp.abs(u_stack))
    with scope("consensus"):
        f = scale_factor(cfg.bits, n, 1.0) / jnp.clip(m, 1e-12, None)
        # the consensus plan is built ONCE from the shared counts and
        # passed into every client's compress (the round-plan engine) —
        # never recomputed inside the vmap.
        plan = build_round_plan(counts, cfg, n, a=a,
                                with_dense_mask=plan_wants_dense_mask(cfg))
    with scope("phase2"):
        if cfg.compact_mode == "block":
            # dense form: summing the per-client compact buffers and scattering
            # the sum back equals masking the dense integer sum — skip the
            # d-sized compact scatter per client (wire paths keep it).
            q_dense, residuals = jax.vmap(
                lambda u, k: _block_compress_dense(u, cfg, f, k, plan))(u_stack,
                                                                        q_keys)
            # the PS's pipelined integer addition (or its §18 order-statistic
            # close — robust_agg.client_sum Python-gates on "sum")
            summed, kept = robust_agg.client_sum(q_dense, cfg)
            delta = jnp.where(plan.keep_dense, summed,
                              0).astype(jnp.float32) / (kept * f)
            return delta, residuals, counts, round_traffic(cfg, d)
        compress = phase2_compress(cfg)
        q_bufs, residuals = jax.vmap(
            lambda u, k: compress(u, cfg, f, k, plan))(u_stack, q_keys)
        # the PS's pipelined integer addition (or the §18 trimmed close)
        summed, kept = robust_agg.client_sum(q_bufs, cfg)
        delta = scatter_sum(summed, plan.idx, plan.keep, cfg, d).astype(jnp.float32) / (kept * f)
    return delta, residuals, counts, round_traffic(cfg, d)


def aggregate_round(u_stack: jax.Array, cfg: FediACConfig, key: jax.Array,
                    *, a=None, probe=None):
    """Run one stacked round on the engine ``cfg.engine`` selects.

    ``cfg.engine`` is a registered name or an ``engines.EngineSpec``:
    ``"monolithic"`` is :func:`aggregate_stack`; ``"stream"`` is the
    chunk-scanned :func:`repro.core.stream_engine.aggregate_stream`
    (DESIGN.md §12); ``"sharded"`` is the coordinate-mesh
    :func:`repro.core.shard_engine.aggregate_shard` (DESIGN.md §16) —
    same signature and return contract, bit-identical outputs.  The FL
    loop, the packet dataplane and the fleet runner all pick the engine
    through this single :mod:`repro.core.engines` dispatch.

    ``probe`` (a ``repro.obs`` RoundProbe) puts a host span around the
    engine call for *eager* callers; it never enters the traced math, so
    outputs are probe-independent (DESIGN.md §15).  Leave it ``None``
    when calling under ``jit``/``vmap``.
    """
    from . import engines
    spec = engines.resolve(cfg)
    if probe is not None and getattr(probe, "enabled", False):
        with probe.span(f"engine-{spec.name}"):
            return engines.run(spec, u_stack, cfg, key, a=a)
    return engines.run(spec, u_stack, cfg, key, a=a)


# ---------------------------------------------------------------------------
# Production: inside shard_map, client axes = mesh axes
# ---------------------------------------------------------------------------

def fediac_allreduce(u: jax.Array, residual: jax.Array, key: jax.Array,
                     cfg: FediACConfig,
                     client_axes: str | Sequence[str] = "data"):
    """Compressed mean of ``u + residual`` over the client mesh axes.

    Must be called inside ``shard_map``.  ``u`` is this client's flat local
    update slice (already sharded over the model axes by the caller);
    ``residual`` the matching error-feedback slice.  Returns
    ``(mean_update, new_residual)``.

    Wire cost per ring hop: d/g uint8 (phase 1) + C*g int32 (phase 2)
    versus 4d bytes for a dense fp32 psum.
    """
    require(cfg.robust_agg == "sum", "robust_agg",
            '"sum" for the allreduce wire path (a psum cannot compute '
            "order statistics in-network; robust modes keep the stacked "
            "and packet engines)", cfg.robust_agg)
    axes = (client_axes,) if isinstance(client_axes, str) else tuple(client_axes)
    d0 = u.shape[-1]
    pad = (-d0) % cfg.vote_chunk
    wdt = jnp.dtype(cfg.work_dtype)
    u = u.astype(wdt) + residual.astype(wdt)
    if pad:
        u = jnp.pad(u, (0, pad))
    d = u.shape[-1]
    # per-client key: fold in the client's linear index along the client axes.
    lin = jnp.int32(0)
    for ax in axes:
        lin = lin * jax.lax.axis_size(ax) + jax.lax.axis_index(ax)
    key = jax.random.fold_in(key, lin)
    kv, kq = jax.random.split(key)
    n = 1
    for ax in axes:
        n *= jax.lax.axis_size(ax)

    # ---- Phase 1: vote, then the "switch" sums 0/1 arrays.
    if cfg.vote_wire == "packed":
        # bit-packed wire: all-gather N x d/8 bytes of packed words, then a
        # local popcount-accumulate (the Pallas vote_popcount kernel's job
        # on real TPU).  Wins when the client count is small (pods).
        from repro.kernels import ops as kops
        n_chunks = d // cfg.vote_chunk
        if cfg.use_pallas and cfg.vote_mode == "threshold":
            # fully fused wire build: |score| >= tau -> packed words in one
            # pass, no intermediate uint8 vote array (kernels/vote_pack).
            scores = jnp.abs(_vote_scores(u, cfg))
            k = max(1, min(cfg.k(n_chunks), n_chunks))
            tau = voting.vote_tau(jnp.max(scores), k, cfg.alpha)
            packed = kops.pack_votes_threshold(scores, tau)
        else:
            packed = kops.pack_votes(_client_votes(u, cfg, kv))
        gathered = packed
        for ax in axes:
            gathered = jax.lax.all_gather(gathered, ax)
        gathered = gathered.reshape(-1, packed.shape[-1])
        counts = kops.count_votes(gathered, n_chunks)
    else:
        votes = _client_votes(u, cfg, kv)
        counts = jax.lax.psum(votes.astype(jnp.dtype(cfg.vote_dtype)),
                              axes).astype(jnp.int32)

    # ---- Scale factor from the global max magnitude (scalar pmax).
    m = jax.lax.pmax(jnp.max(jnp.abs(u)), axes)
    f = scale_factor(cfg.bits, n, 1.0) / jnp.clip(m, 1e-12, None)

    # ---- Phase 2: the consensus plan is a deterministic function of the
    # psum'd counts, so every client builds the identical plan (this IS the
    # paper's GIA broadcast); compress + integer psum of C entries.
    plan = build_round_plan(counts, cfg, n,
                            with_dense_mask=plan_wants_dense_mask(cfg))
    compress = phase2_compress(cfg)
    q_buf, new_residual = compress(u, cfg, f, kq, plan)
    summed = jax.lax.psum(q_buf, axes)
    if cfg.compact_mode == "block":
        mean = compaction.block_scatter(summed, plan.keep_dense, plan.pos, d,
                                        cfg.block_size, cfg.capacity_frac)
        mean = mean.astype(jnp.float32) / (n * f)
    else:
        # de-quantize the compact buffer first: the d-sized scatter result
        # then lives in the working dtype, not int32.
        mean_buf = (summed.astype(jnp.float32) / (n * f)).astype(wdt)
        mean = scatter_sum(mean_buf, plan.idx, plan.keep, cfg, d)
    if pad:
        mean = mean[:d0]
        new_residual = new_residual[:d0]
    return mean, new_residual


def dense_allreduce(u: jax.Array, residual: jax.Array, key: jax.Array,
                    cfg: FediACConfig | None = None,
                    client_axes: str | Sequence[str] = "data"):
    """Uncompressed FedAvg mean — the dense baseline with the same signature."""
    axes = (client_axes,) if isinstance(client_axes, str) else tuple(client_axes)
    mean = jax.lax.pmean((u + residual).astype(jnp.float32), axes)
    return mean, jnp.zeros_like(residual)
