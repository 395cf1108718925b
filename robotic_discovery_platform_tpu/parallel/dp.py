"""Distributed training steps over a device mesh.

Two equivalent data-parallel paths (SURVEY.md section 2.3's rebuild mapping),
both running their collectives over ICI (or DCN across slices):

1. ``parallelize_training`` -- the pjit idiom: jit the single-device step
   with explicit in/out shardings (batch over "data", optional tensor-
   parallel kernel sharding over "model", optional spatial sharding of H).
   XLA's SPMD partitioner inserts the gradient all-reduce (and halo
   exchanges for spatially-sharded convs) automatically.

2. ``shard_map_train_step`` -- the explicit-collectives idiom: shard_map the
   per-device step and ``jax.lax.pmean`` the gradients across "data" by
   hand. Numerically identical; exists so the collective plane is visible
   and testable (the NCCL-allreduce role, SURVEY.md section 5.8).
"""

from __future__ import annotations

from typing import Callable

import jax
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from robotic_discovery_platform_tpu.analysis import recompile
from robotic_discovery_platform_tpu.parallel import mesh as mesh_lib
from robotic_discovery_platform_tpu.utils import transferguard

def _state_shardings(mesh: Mesh, state, tp: bool, tp_min_channels: int):
    """Sharding tree for TrainState: params (and matching opt_state moments)
    optionally tensor-parallel, counters replicated."""
    rep = P()
    if not tp:
        return jax.tree.map(lambda _: rep, state)

    pspecs = mesh_lib.tp_param_specs(state.params, tp_min_channels)

    def opt_specs(entry):
        # optax.adam state: ScaleByAdamState(mu, nu) pytrees mirror params
        try:
            return jax.tree.map(
                lambda ps, _: ps, pspecs, entry,
                is_leaf=lambda x: isinstance(x, P),
            )
        except (ValueError, TypeError):
            return jax.tree.map(lambda _: rep, entry)

    def map_opt(o):
        if hasattr(o, "mu") and hasattr(o, "nu"):
            return o._replace(
                mu=opt_specs(o.mu), nu=opt_specs(o.nu),
                count=rep,
            )
        return jax.tree.map(lambda _: rep, o)

    opt_state = tuple(map_opt(o) for o in state.opt_state)
    return state.replace(
        params=pspecs,
        opt_state=opt_state,
        batch_stats=jax.tree.map(lambda _: rep, state.batch_stats),
        epoch=rep,
        best_val_loss=rep,
    )


def put_global_batch(mesh: Mesh, x, spatial: bool = False):
    """Place a host-side global batch onto the mesh's "data" axis.

    Single-process: a plain transfer (GSPMD shards it). Multi-host: every
    process holds the same global batch (loaders are seed-deterministic),
    and each materializes exactly the shards its local devices own via
    ``jax.make_array_from_callback`` -- fully general over the mesh
    layout, including data axes smaller than the process count (a data
    shard replicated across several hosts) and spatial/tensor axes that
    split a host's devices across non-contiguous row blocks. The earlier
    contiguous-row-block scheme rejected those layouts by construction
    (round-3 verdict item 9).
    """
    import numpy as np

    import jax.numpy as jnp

    sharding = mesh_lib.batch_sharding(mesh, spatial=spatial)
    if jax.process_count() == 1:
        return jax.device_put(jnp.asarray(x), sharding)
    data = dict(mesh.shape).get("data", 1)
    if x.shape[0] % data:
        raise ValueError(
            f"global batch {x.shape[0]} not divisible by the data axis "
            f"({data})"
        )
    x = np.asarray(x)
    return jax.make_array_from_callback(x.shape, sharding, lambda idx: x[idx])


def parallelize_training(
    mesh: Mesh,
    model,
    tx,
    loss_fn: Callable,
    state,
    donate: bool = True,
    tp: bool | None = None,
    tp_min_channels: int = 256,
    spatial: bool | None = None,
):
    """Return (train_step, eval_step, sharded_state) running SPMD over the
    mesh. ``tp``/``spatial`` default to "on iff the mesh axis is >1"."""
    from robotic_discovery_platform_tpu.training.trainer import (
        core_eval_step,
        core_train_step,
    )

    # Treat a missing mesh axis as size 1 so user-supplied meshes with only a
    # "data" axis default tp/spatial off instead of raising KeyError.
    tp = dict(mesh.shape).get("model", 1) > 1 if tp is None else tp
    spatial = (
        dict(mesh.shape).get("spatial", 1) > 1 if spatial is None else spatial
    )

    state_specs = _state_shardings(mesh, state, tp, tp_min_channels)
    state_shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s), state_specs,
        is_leaf=lambda x: isinstance(x, P),
    )
    batch_sh = mesh_lib.batch_sharding(mesh, spatial=spatial)

    sharded_state = jax.tree.map(jax.device_put, state, state_shardings)

    train = transferguard.apply(jax.jit(
        recompile.trace_guard("parallel.train_step", budget=3)(
            core_train_step(model, tx, loss_fn)
        ),
        in_shardings=(state_shardings, batch_sh, batch_sh),
        out_shardings=(state_shardings, NamedSharding(mesh, P())),
        donate_argnums=(0,) if donate else (),
    ))
    evals = transferguard.apply(jax.jit(
        recompile.trace_guard("parallel.eval_step", budget=3)(
            core_eval_step(model, loss_fn)
        ),
        in_shardings=(state_shardings, batch_sh, batch_sh),
        out_shardings=NamedSharding(mesh, P()),
    ))
    return train, evals, sharded_state


def shard_map_train_step(mesh: Mesh, model, tx, loss_fn: Callable,
                         donate: bool = True):
    """Explicit-collective DP step: per-device forward/backward, manual
    ``pmean`` over the "data" axis, replicated update on every device."""

    def per_device(state, x, y):
        def compute(params):
            variables = {"params": params}
            if state.batch_stats:
                variables["batch_stats"] = state.batch_stats
                logits, updates = model.apply(
                    variables, x, train=True, mutable=["batch_stats"]
                )
            else:
                logits, updates = model.apply(variables, x, train=True), {}
            return loss_fn(logits, y), updates

        (loss, updates), grads = jax.value_and_grad(compute, has_aux=True)(
            state.params
        )
        # The collective plane: gradient allreduce over ICI.
        grads = jax.lax.pmean(grads, axis_name="data")
        loss = jax.lax.pmean(loss, axis_name="data")
        new_stats = updates.get("batch_stats", state.batch_stats)
        if new_stats:
            new_stats = jax.lax.pmean(new_stats, axis_name="data")
        grad_updates, opt_state = tx.update(grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, grad_updates)
        return (
            state.replace(params=params, opt_state=opt_state,
                          batch_stats=new_stats),
            loss,
        )

    rep = P()

    def step(state, x, y):
        specs_state = jax.tree.map(lambda _: rep, state)
        # the per-device step mutates batch-stat averages, so the
        # replication check is off
        mapped = jax.shard_map(
            per_device,
            mesh=mesh,
            in_specs=(specs_state, P("data"), P("data")),
            out_specs=(specs_state, rep),
            check_vma=False,
        )
        return mapped(state, x, y)

    return transferguard.apply(jax.jit(
        recompile.trace_guard("parallel.shard_map_train_step", budget=3)(
            step
        ),
        donate_argnums=(0,) if donate else (),
    ))
