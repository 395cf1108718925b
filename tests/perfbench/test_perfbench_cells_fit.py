"""Each cell's train step, as its driver's ``abstract_step`` hands it
over, compiled here for a described (not attached) ``v5e:2x2`` chip at the
cell's own shapes: the compiler's own byte count stays
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


def cases(root: Path = ROOT) -> list:
    """One case for each (configuration, traffic) pair of ``root``'s
    ``BENCHMARK.json``, named by the cells that share it; the case holds
    the first of them, whose driver is asked for the step."""
    seen = {}
    for w in spec.Bench(root).doc["workloads"]:
        seen.setdefault((w["config"], w["traffic"]), []).append(w["name"])
    return [pytest.param(cells[0], id=f"{c}-{t}:" + "+".join(cells))
            for (c, t), cells in seen.items()]


def abstract_step(root: Path, workload: str):
    """(fn, args) from the driver that the cell's traffic names: the
    function the timed program runs for one optimiser step, and its
    arguments as shapes."""
    bench = spec.Bench(root)
    cell = bench.cell(workload, 0, 0.0, root / ".perfbench_runs")
    return bench.driver(cell.traffic["driver"]).abstract_step(cell)


@pytest.mark.parametrize("workload", cases())
def test_train_step_holds_over_the_floor(one_chip, no_compile_cache,
                                         workload):
    import jax

    fn, args = abstract_step(ROOT, workload)
    placed = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), args)
    mem = jax.jit(fn).lower(*placed).compile().memory_analysis()
    held = mem.temp_size_in_bytes + mem.argument_size_in_bytes
    print(f"{workload}: {mem.temp_size_in_bytes / 2**30:.2f} GiB of "
          f"temporaries + {mem.argument_size_in_bytes / 2**30:.2f} of "
          "arguments")
    assert held > FLOOR, f"{workload}: {held / 2**30:.2f} GiB"
