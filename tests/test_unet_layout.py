"""The bilinear decoder's up-sampling leaves the layout of the
full-resolution activations to the convolutions (PERF.md section 6, PR 36).

Compiled here for a described (not attached) ``v5e:2x2`` chip, nothing
runs: the two full-resolution blocks of the deployed model at the
retraining cell's shapes, ``inc`` (the DoubleConv that makes the skip) and
the last ``Up``, forward and backward; the smallest program that showed the
fault (the ``Up`` block alone, fed a skip from outside, did not). Written as
``"Hh,bhwc->bHwc"`` then ``"Ww,bhwc->bhWc"`` the two interpolation products
made XLA's layout assignment give ReLU, the norms' sums and the concatenate
one layout and the convolutions another, and every 64-channel 256x256
activation of both blocks was copied between the two: twelve 268 MB
``copy`` operations in this program, a fifth of the whole step's device
time on the chip. The guard reads the compiler's own text, so a later
change of the form, of ``Up`` or of the compiler that brings the copies back
is seen on the CPU.
"""

import os
import re
import threading

import jax
import jax.numpy as jnp
import pytest
from flax import linen as nn

from robotic_discovery_platform_tpu.models import unet

BATCH, SIZE, WIDTH = 32, 256, 64
TIME_LIMIT_S = 240.0


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here, or another process has it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)


def _within(seconds: float, fn):
    """``fn()`` on a daemon thread, failing the test (and leaving the
    thread behind) where it has not returned in time: the compiler holds
    no Python frame a signal could interrupt."""
    box = {}

    def run():
        try:
            box["value"] = fn()
        except BaseException as e:  # handed to the test's thread
            box["error"] = e

    thread = threading.Thread(target=run, daemon=True)
    thread.start()
    thread.join(seconds)
    if thread.is_alive():
        pytest.fail(f"still compiling after {seconds:.0f} s")
    if "error" in box:
        raise box["error"]
    return box["value"]


def _entry_copies(hlo: str) -> list:
    """(shape, scope path) of every ``copy`` of the entry computation."""
    entry = hlo[hlo.index("\nENTRY "):]
    found = []
    for line in entry[:entry.index("\n}")].splitlines():
        m = re.search(r" = \w+\[([\d,]*)\]\S* copy\(", line)
        if m:
            scope = re.search(r'op_name="([^"]*)"', line)
            found.append((m.group(1), scope.group(1) if scope else ""))
    return found


class _FullResolutionBlocks(nn.Module):
    """``UNet``'s ``inc`` and ``up4`` with what lies between them left out:
    the decoder's input arrives as an argument."""

    @nn.compact
    def __call__(self, image, below, train: bool = False):
        skip = unet.DoubleConv(WIDTH, dtype=jnp.bfloat16)(
            image.astype(jnp.bfloat16), train)
        return unet.Up(WIDTH, bilinear=True, dtype=jnp.bfloat16)(
            below, skip, train)


def test_no_full_resolution_copy_inside_a_double_conv(one_chip,
                                                      no_compile_cache):
    blocks = _FullResolutionBlocks()
    image = jax.ShapeDtypeStruct((BATCH, SIZE, SIZE, 3), jnp.float32,
                                 sharding=one_chip)
    below = jax.ShapeDtypeStruct((BATCH, SIZE // 2, SIZE // 2, WIDTH),
                                 jnp.bfloat16, sharding=one_chip)
    variables = jax.eval_shape(lambda: blocks.init(
        jax.random.key(0), jnp.zeros(image.shape, image.dtype),
        jnp.zeros(below.shape, below.dtype)))
    variables = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), variables)

    def loss(params, stats, image, below):
        y, _ = blocks.apply({"params": params, "batch_stats": stats}, image,
                            below, train=True, mutable=["batch_stats"])
        return jnp.mean(jnp.square(y.astype(jnp.float32)))

    step = jax.jit(jax.grad(loss, argnums=(0, 3)))
    compiled = _within(TIME_LIMIT_S, lambda: step.lower(
        variables["params"], variables["batch_stats"], image,
        below).compile())
    copies = _entry_copies(compiled.as_text())
    full = f"{BATCH},{SIZE},{SIZE},{WIDTH}"
    inside = [scope for shape, scope in copies
              if shape == full and "DoubleConv" in scope]
    assert not inside, (
        f"{len(inside)} copies of a [{full}] activation inside a "
        "DoubleConv: " + "; ".join(inside))
