"""Production distributed train step for the (16,16)/(2,16,16) meshes.

Client topology (DESIGN.md §2/§4):

* **replica mode** (``cfg.fsdp=False``): clients = the ``data`` axis
  (x ``pod``).  Params are replicated over the client axes and
  model-parallel over ``model``; E>=1 local SGD steps run per client.

* **pod mode** (``cfg.fsdp=True``: deepseek-236b, command-r-104b,
  chameleon-34b): a client is a whole pod (cross-silo FL; the paper's
  multi-PS future-work topology).  Params are FSDP-sharded over
  (model, data) *within* a pod and replicated across pods; E=1.  On the
  single-pod mesh there is one client and the step degenerates to plain
  FSDP training (recorded as such in the roofline table).

Both modes share one mechanism: per-client updates are materialized with a
leading client dimension via ``vmap`` (the client axes shard that dim), then
aggregated in a **fully-manual** ``shard_map`` over the whole mesh — every
device ravels its local parameter shard into one flat vector and runs
FediAC phase 1/2 with explicit integer ``psum``s over the client axes.
Each device is literally one programmable switch for its slice of the
coordinates; the ``model``(+``data`` under FSDP) axes shard the PS, the
client axes are the clients.  All compaction gathers are device-local, so
no GSPMD partitioning happens inside the aggregation.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.fediac import dense_allreduce, fediac_allreduce
from repro.models import loss_fn, param_specs
from repro.models.model import init_params
from repro.models.shardings import set_activation_sharding


# ---------------------------------------------------------------------------
# topology helpers
# ---------------------------------------------------------------------------

def client_axes_for(cfg, mesh) -> tuple[str, ...]:
    multi_pod = "pod" in mesh.axis_names
    if cfg.fsdp:
        return ("pod",) if multi_pod else ()
    return ("pod", "data") if multi_pod else ("data",)


def n_clients_for(cfg, mesh) -> int:
    n = 1
    for ax in client_axes_for(cfg, mesh):
        n *= mesh.shape[ax]
    return n


def data_axes_for(mesh) -> tuple[str, ...]:
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


# ---------------------------------------------------------------------------
# microbatched per-client local update
# ---------------------------------------------------------------------------

def _microbatched_grad(cfg, params, batch, n_micro: int, constrain=None):
    """Mean loss gradient over a client batch, scanned over microbatches.

    ``constrain`` re-asserts the batch sharding after the microbatch
    reshape — without it GSPMD can lose the batch partitioning and
    replicate per-microbatch activations/logits across the data axis.
    """
    b = batch["tokens"].shape[0]
    n_micro = min(n_micro, b)
    assert b % n_micro == 0, (b, n_micro)

    mbs = {k: v.reshape(n_micro, b // n_micro, *v.shape[1:]) for k, v in batch.items()}
    if constrain is not None:
        mbs = constrain(mbs)
    gfn = jax.value_and_grad(lambda p, mb: loss_fn(p, cfg, mb))

    def body(carry, mb):
        loss_acc, g_acc = carry
        loss, g = gfn(params, mb)
        g_acc = jax.tree_util.tree_map(lambda a, x: a + x.astype(a.dtype), g_acc, g)
        return (loss_acc + loss, g_acc), None

    g0 = jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.dtype(cfg.grad_dtype)), params)
    (loss, g), _ = jax.lax.scan(body, (jnp.zeros(()), g0), mbs)
    scale = 1.0 / n_micro
    return loss * scale, jax.tree_util.tree_map(lambda x: x * scale, g)


def _local_update(cfg, params, client_batch, lr: float, constrain=None,
                  n_micro: int | None = None):
    """One client's upload U = w_0 - w_E after E local SGD steps (Algo. 1
    lines 3-4).  E=1 reduces to lr * grad."""
    e = max(1, cfg.fl_local_steps)
    n_micro = cfg.microbatch if n_micro is None else n_micro

    if e == 1:
        loss, g = _microbatched_grad(cfg, params, client_batch, n_micro,
                                     constrain)
        return jax.tree_util.tree_map(lambda gg: lr * gg, g), loss

    def step(w, _):
        loss, g = _microbatched_grad(cfg, w, client_batch, n_micro, constrain)
        w = jax.tree_util.tree_map(
            lambda p, gg: (p.astype(jnp.float32) - lr * gg).astype(p.dtype), w, g)
        return w, loss

    w_final, losses = jax.lax.scan(step, params, None, length=e)
    update = jax.tree_util.tree_map(
        lambda a, b_: a.astype(jnp.float32) - b_.astype(jnp.float32),
        params, w_final)
    return update, losses.mean()


def _aggregate_flat(cfg, flat_u, flat_res, key, client_axes):
    if cfg.aggregator == "dense":
        return dense_allreduce(flat_u, flat_res, key, client_axes=client_axes)
    if cfg.aggregator == "switchml":
        from repro.core.mesh_baselines import switchml_allreduce
        return switchml_allreduce(flat_u, flat_res, key, cfg.fediac,
                                  client_axes=client_axes)
    if cfg.aggregator == "topk":
        from repro.core.mesh_baselines import topk_allreduce
        return topk_allreduce(flat_u, flat_res, key, cfg.fediac,
                              client_axes=client_axes)
    return fediac_allreduce(flat_u, flat_res, key, cfg.fediac,
                            client_axes=client_axes)


# ---------------------------------------------------------------------------
# step factory
# ---------------------------------------------------------------------------

@dataclass
class TrainStepBundle:
    step: callable        # (params, residual, batch, key) -> (params, residual, metrics)
    params_spec: object   # PartitionSpec pytrees for jit in_shardings
    residual_spec: object
    batch_spec: dict
    n_clients: int
    mode: str             # replica | pod | plain


def make_train_step(cfg, mesh, *, lr: float = 1e-2,
                    use_pallas: bool | None = None) -> TrainStepBundle:
    if use_pallas is not None:
        from dataclasses import replace as _replace
        cfg = cfg.with_(fediac=_replace(cfg.fediac, use_pallas=use_pallas))
    model_size = mesh.shape["model"]
    data_size = mesh.shape["data"]
    axes = client_axes_for(cfg, mesh)
    n_clients = n_clients_for(cfg, mesh)
    dax = data_axes_for(mesh)
    mode = ("pod" if axes == ("pod",) else "replica") if axes else "plain"

    pshape = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))
    pspec = param_specs(pshape, cfg, model_size=model_size, data_size=data_size)
    # residual: per-client error feedback with a leading client dim.  Plain
    # mode (single client, no aggregation) carries a scalar placeholder.
    if axes:
        res_spec = jax.tree_util.tree_map(lambda s: P(axes, *tuple(s)), pspec)
    else:
        res_spec = P()
    bspec = {"tokens": P(dax, None), "targets": P(dax, None)}
    if cfg.is_enc_dec:
        bspec["frames"] = P(dax, None, None)

    # Residual-stream constraint: batch over the non-client data axes,
    # features over `model` for FSDP archs (checkpoint storage /mesh-size).
    if cfg.fsdp:
        act_batch = tuple(a for a in dax if a not in axes) or None
        feat = "model" if cfg.act_shard == "feature" else None
        seq = "model" if cfg.act_shard == "sequence" else None
        set_activation_sharding(mesh, act_batch, feat, seq)
    else:
        # replica mode: the client vmap dim already carries the batch
        # sharding; no constraint needed.
        set_activation_sharding(None, None, None)

    if not axes:
        step = _make_plain_step(cfg, lr, mesh, dax)
    else:
        step = _make_fl_step(cfg, mesh, pspec, res_spec, axes, n_clients, lr)
    return TrainStepBundle(step, pspec, res_spec, bspec, n_clients, mode)


def _mb_constrainer(mesh, dax):
    """Constraint: microbatch dicts keep their batch dim sharded over dax."""
    def constrain(mbs):
        return jax.lax.with_sharding_constraint(
            mbs, {k: NamedSharding(mesh, P(None, dax, *([None] * (v.ndim - 2))))
                  for k, v in mbs.items()})
    return constrain


def _make_plain_step(cfg, lr, mesh=None, dax=("data",)):
    """Single-pod FSDP: one client -> plain data-parallel training."""
    constrain = _mb_constrainer(mesh, dax) if mesh is not None else None
    rows = mesh.shape["data"] if mesh is not None else 1

    def step(params, residual, batch, key):
        gb = batch["tokens"].shape[0]
        n_micro = max(1, min(cfg.microbatch, gb))
        loss, g = _microbatched_grad(cfg, params, batch, n_micro, constrain)
        new_params = jax.tree_util.tree_map(
            lambda p, gg: (p.astype(jnp.float32) - lr * gg).astype(p.dtype),
            params, g)
        return new_params, residual, {"loss": loss, "update_norm": _tree_norm(g)}

    return step


def _make_fl_step(cfg, mesh, pspec, res_spec, axes, n_clients, lr):
    ustack_spec = jax.tree_util.tree_map(lambda s: P(axes, *tuple(s)), pspec)
    # batch per client: dim0 = clients over the client axes; the within-client
    # batch dim is data-sharded in pod mode (within-pod data parallelism).
    inner_b = None if "data" in axes else "data"
    # in pod mode each microbatch must still cover the inner data axis —
    # fewer sequences than data rows forces GSPMD into replication thrash.
    mb_cap_rows = mesh.shape["data"] if inner_b is not None else 1

    def _bstack_spec(v_ndim):
        return P(axes, inner_b, *([None] * (v_ndim - 2)))

    # pod mode: within-client batches stay data-sharded through the
    # microbatch reshape (applied inside the per-client vmap).
    if inner_b is not None:
        def constrain(mbs):
            return jax.lax.with_sharding_constraint(
                mbs, {k: NamedSharding(mesh, P(None, inner_b,
                                               *([None] * (v.ndim - 2))))
                      for k, v in mbs.items()})
    else:
        constrain = None

    def agg(u_stack, res_stack, key):
        rdt = jnp.dtype(cfg.residual_dtype)

        def local(u_loc, r_loc, k):
            sq = jax.tree_util.tree_map(lambda x: x[0], u_loc)   # drop client dim
            rq = jax.tree_util.tree_map(lambda x: x[0], r_loc)
            if cfg.fediac.granularity == "tensor" and cfg.aggregator != "dense":
                # per-leaf aggregation: peak memory tracks the largest
                # tensor, not the whole raveled shard (DESIGN.md §2).
                leaves_u, treedef = jax.tree_util.tree_flatten(sq)
                leaves_r = jax.tree_util.tree_leaves(rq)
                keys = jax.random.split(k, len(leaves_u))
                means, new_rs = [], []
                for lu, lr_, lk in zip(leaves_u, leaves_r, keys):
                    m, nr_ = _aggregate_flat(cfg, lu.reshape(-1),
                                             lr_.reshape(-1), lk, axes)
                    means.append(m.reshape(lu.shape).astype(lu.dtype))
                    new_rs.append(nr_.reshape(lu.shape)[None].astype(rdt))
                return (jax.tree_util.tree_unflatten(treedef, means),
                        jax.tree_util.tree_unflatten(treedef, new_rs))
            flat_u, unravel = ravel_pytree(sq)
            flat_r, _ = ravel_pytree(rq)
            mean, new_res = _aggregate_flat(cfg, flat_u, flat_r, k, axes)
            nr = jax.tree_util.tree_map(lambda x: x[None].astype(rdt),
                                        unravel(new_res))
            return unravel(mean), nr

        return jax.shard_map(local, mesh=mesh,
                             in_specs=(ustack_spec, res_spec, P()),
                             out_specs=(pspec, res_spec),
                             check_vma=False)(u_stack, res_stack, key)

    def step(params, residual, batch, key):
        gb = batch["tokens"].shape[0]
        per_client_b = gb // n_clients
        n_micro = max(1, min(cfg.microbatch, per_client_b))
        per_client = {k: v.reshape(n_clients, per_client_b, *v.shape[1:])
                      for k, v in batch.items()}
        per_client = jax.lax.with_sharding_constraint(
            per_client, {k: NamedSharding(mesh, _bstack_spec(v.ndim))
                         for k, v in per_client.items()})
        updates, losses = jax.vmap(
            lambda cb: _local_update(cfg, params, cb, lr, constrain, n_micro),
            spmd_axis_name=axes)(per_client)
        updates = jax.lax.with_sharding_constraint(
            updates, jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), ustack_spec))
        mean_u, new_res = agg(updates, residual, key)
        new_params = jax.tree_util.tree_map(
            lambda p, u: (p.astype(jnp.float32) - u.astype(jnp.float32)).astype(p.dtype),
            params, mean_u)
        metrics = {"loss": losses.mean(), "update_norm": _tree_norm(mean_u)}
        return new_params, new_res, metrics

    return step


def _tree_norm(t):
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree_util.tree_leaves(t)))
