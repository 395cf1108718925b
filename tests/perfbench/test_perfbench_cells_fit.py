"""Each cell's train step, compiled here for a described (not attached)
``v5e:2x2`` chip at the cell's batch: the compiler's own byte count stays
over the driver's floor of 4 GiB (25% of a chip), so a later change that
shrinks a cell under it is seen on the CPU. Nothing runs; these are not
chip measurements. All such compiles live in this one file (one process
may hold the TPU compiler's library)."""

import os
from pathlib import Path

import pytest

from perfbench.lib import spec

ROOT = Path(__file__).resolve().parents[2]
FLOOR = 4 * 2 ** 30


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)


def _configs():
    bench = spec.Bench(ROOT)
    seen = {}
    for w in bench.doc["workloads"]:
        batch = bench.traffic(w["traffic"])["train"]["batch_size"]
        seen.setdefault((w["config"], batch), []).append(w["name"])
    return [pytest.param(c, b, id=f"{c}-b{b}:" + "+".join(cells))
            for (c, b), cells in seen.items()]


@pytest.mark.parametrize("config,batch", _configs())
def test_train_step_holds_over_the_floor(one_chip, no_compile_cache, config,
                                         batch):
    import jax
    import jax.numpy as jnp
    import optax

    from robotic_discovery_platform_tpu.models import losses
    from robotic_discovery_platform_tpu.models.unet import build_unet
    from robotic_discovery_platform_tpu.training import trainer
    from robotic_discovery_platform_tpu.utils.config import ModelConfig

    body = spec.Bench(ROOT).config(config)
    # the XLA convolution path: what "auto" resolves to at this volume on a
    # TPU (this process sees a CPU and would resolve it otherwise)
    model = build_unet(ModelConfig(**body["model"], conv_impl="flax"))
    size = body["train"]["img_size"]
    tx = optax.adam(body["train"]["learning_rate"])
    state = jax.eval_shape(
        lambda: trainer.create_state(model, tx, jax.random.key(0), size))
    place = lambda a: jax.ShapeDtypeStruct(  # noqa: E731
        a.shape, a.dtype, sharding=one_chip)
    step = trainer.core_train_step(model, tx, losses.make_loss_fn("bce"))
    compiled = jax.jit(step).lower(
        jax.tree.map(place, state),
        place(jax.ShapeDtypeStruct((batch, size, size, 3), jnp.float32)),
        place(jax.ShapeDtypeStruct((batch, size, size, 1), jnp.float32)),
    ).compile()
    mem = compiled.memory_analysis()
    held = mem.temp_size_in_bytes + mem.argument_size_in_bytes
    assert held > FLOOR, f"{config} at batch {batch}: {held / 2**30:.2f} GiB"
