"""Device meshes and sharding vocabulary.

The reference is strictly single-device (SURVEY.md section 2.3: no DP/TP/PP,
no collective backend; the only IPC is gRPC). This module is where the
TPU-native framework grows its distributed spine: a named
``jax.sharding.Mesh`` whose axes name the kinds of parallelism --

- ``data``    data parallelism: batch sharding, gradient allreduce over ICI;
- ``spatial`` spatial/context parallelism: H-dimension activation sharding
              (XLA inserts halo exchanges for convolutions) -- the conv-net
              analogue of sequence/ring parallelism for this workload
              (SURVEY.md section 5.7: the scaling dimension here is spatial);
- ``model``   tensor parallelism: output-channel sharding of the widest conv
              kernels.

Multi-host initialization goes through ``jax.distributed.initialize`` (the
idiomatic replacement for the NCCL/MPI role, SURVEY.md section 5.8); the mesh
then spans all hosts' devices and the same code runs ICI-local or cross-host
over DCN.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from robotic_discovery_platform_tpu.utils.config import MeshConfig

AXES = ("data", "spatial", "model")


def initialize_distributed(coordinator: str | None = None,
                           num_processes: int | None = None,
                           process_id: int | None = None) -> None:
    """Multi-host bring-up (no-op on a single host): wires this process into
    the global device mesh over ICI/DCN."""
    if num_processes is None or num_processes <= 1:
        return
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=num_processes,
        process_id=process_id,
    )


def make_mesh(cfg: MeshConfig = MeshConfig(), devices=None) -> Mesh:
    """Build a ("data", "spatial", "model") mesh. Axis sizes <= 0 are
    inferred from the device count; sizes must multiply to #devices."""
    devices = list(jax.devices() if devices is None else devices)
    n = len(devices)
    data, spatial, model = cfg.data, cfg.spatial, cfg.model
    spatial = max(1, spatial)
    model = max(1, model)
    if data <= 0:
        if n % (spatial * model):
            raise ValueError(
                f"cannot infer data axis: {n} devices not divisible by "
                f"spatial*model={spatial * model}"
            )
        data = n // (spatial * model)
    if data * spatial * model != n:
        raise ValueError(
            f"mesh {data}x{spatial}x{model} != {n} available devices"
        )
    arr = np.asarray(devices).reshape(data, spatial, model)
    return Mesh(arr, AXES)


def make_serving_mesh(chips: int = 0, devices=None) -> Mesh:
    """The serving router's mesh: ``chips`` devices along the "data" axis
    (spatial = model = 1). ``chips`` <= 0 takes every available device.
    Serving parallelism is pure data parallelism -- each dispatch is an
    independent padded batch -- so the serving mesh never needs the
    spatial/model axes the training mesh carries."""
    devices = list(jax.devices() if devices is None else devices)
    if chips > 0:
        if chips > len(devices):
            raise ValueError(
                f"serving mesh wants {chips} chips but only "
                f"{len(devices)} devices are available"
            )
        devices = devices[:chips]
    return make_mesh(MeshConfig(data=len(devices)), devices)


def device_ring(mesh: Mesh) -> tuple:
    """The mesh's devices flattened in data-major order: the ring the
    serving router round-robins dispatches over."""
    return tuple(mesh.devices.reshape(-1))


def chip_shardings(mesh: Mesh) -> tuple:
    """One single-device sharding per ring position: the placement a
    round-robin dispatch uses for its per-chip ``device_put``."""
    from jax.sharding import SingleDeviceSharding

    return tuple(SingleDeviceSharding(d) for d in device_ring(mesh))


def least_loaded(loads, start: int = 0) -> int:
    """Index of the minimum of ``loads``, ties broken in ring order from
    ``start``: with all chips idle consecutive picks walk the ring
    (round-robin), under skewed load the emptiest chip wins."""
    n = len(loads)
    best = start % n
    for off in range(1, n):
        i = (start + off) % n
        if loads[i] < loads[best]:
            best = i
    return best


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def batch_sharding(mesh: Mesh, spatial: bool = False) -> NamedSharding:
    """NHWC batches: batch over "data", optionally H over "spatial"."""
    if spatial:
        return NamedSharding(mesh, P("data", "spatial", None, None))
    return NamedSharding(mesh, P("data"))


def tp_param_specs(params, min_channels: int = 256):
    """Tensor-parallel PartitionSpecs for a conv-param tree: shard the
    output-channel (last) dimension of every kernel at least
    ``min_channels`` wide over the "model" axis; everything else replicated.

    Returns a pytree of PartitionSpec matching ``params``.
    """

    def spec(path, leaf):
        if (
            leaf.ndim >= 2
            and leaf.shape[-1] >= min_channels
            and path
            and path[-1].key == "kernel"
    ):
            return P(*([None] * (leaf.ndim - 1) + ["model"]))
        return P()

    return jax.tree_util.tree_map_with_path(spec, params)


def shard_pytree(mesh: Mesh, tree, specs=None):
    """Place a pytree onto the mesh (replicated by default, or per-leaf
    specs)."""
    if specs is None:
        sharding = replicated(mesh)
        return jax.tree.map(lambda a: jax.device_put(a, sharding), tree)
    return jax.tree.map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)), tree, specs
    )
