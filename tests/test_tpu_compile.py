"""Compile the main path's Pallas kernels for a described TPU v5e.

No chip is needed: the TPU compiler is installed and compiles for a
topology that is described, not attached.  What it refuses here (a cast
Mosaic cannot lower, a gather it does not support, a block that does not
fit VMEM) the chip would refuse too, and interpret mode never sees.  Each
test asserts that a Mosaic kernel (``tpu_custom_call``) is in the compiled
program, so that neither interpret mode nor the jnp oracle was compiled.

Shapes are a deployment's: width 31 * 1024 (the paper's k x 31 for
k = 1024), 5 rows, one 8-stream block, 4096 events per stream; and one
stream alone, as each shard of a pipeline plane runs.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.countsketch_query import countsketch_query_batched
from repro.kernels.countsketch_scatter import countsketch_scatter_batched
from repro.kernels.countsketch_update import countsketch_update_batched
from repro.kernels.ppswor_transform import ppswor_transform

WIDTH, ROWS, STREAMS, EVENTS = 31 * 1024, 5, 8, 4096


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compile cache
    off: entries written for a described chip cannot be read back here."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _assert_mosaic(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            for shape, dtype in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo


_STREAM_PARAMS = (((STREAMS,), jnp.uint32), ((STREAMS,), jnp.uint32),
                  ((STREAMS,), jnp.int32))


@pytest.mark.parametrize("p,scheme", [(1.0, "ppswor"), (2.0, "priority")])
def test_scatter_batched_compiles(one_chip, p, scheme):
    def scatter(keys, vals, seeds, tseeds, lengths):
        return countsketch_scatter_batched(
            keys, vals, ROWS, WIDTH, seeds, p=p, scheme=scheme,
            transform_seeds=tseeds, lengths=lengths, interpret=False)

    _assert_mosaic(scatter, one_chip, ((STREAMS, EVENTS), jnp.int32),
                   ((STREAMS, EVENTS), jnp.float32), *_STREAM_PARAMS)


def test_update_batched_compiles(one_chip):
    def update(vals, seeds, tseeds, lengths, base_keys):
        return countsketch_update_batched(
            vals, ROWS, WIDTH, seeds, p=1.0, transform_seeds=tseeds,
            base_keys=base_keys, lengths=lengths, interpret=False)

    _assert_mosaic(update, one_chip, ((STREAMS, EVENTS), jnp.float32),
                   *_STREAM_PARAMS, ((STREAMS,), jnp.uint32))


# 1024: a k = 1024 sample's keys; 8192: a candidate refresh (4k candidates
# plus a 4096-event block); 5120: the tenants deployment's refresh (4k
# candidates plus a 1024-event block), also at a width that is no multiple
# of 1024, and at k = 4096's width, whose table block outgrows the default
# scoped VMEM
@pytest.mark.parametrize("keys,width", [
    pytest.param(1024, WIDTH, id="1024"),
    pytest.param(8192, WIDTH, id="8192"),
    pytest.param(5120, WIDTH, id="5120"),
    pytest.param(5120, 30_000, id="5120-w30000"),
    pytest.param(4096, 31 * 4096, id="4096-w126976"),
])
def test_query_batched_compiles(one_chip, keys, width):
    def query(tables, qkeys, seeds):
        return countsketch_query_batched(tables, qkeys, seeds,
                                         interpret=False)

    _assert_mosaic(query, one_chip, ((STREAMS, ROWS, width), jnp.float32),
                   ((STREAMS, keys), jnp.int32), ((STREAMS,), jnp.uint32))


# one stream, as each shard of a pipeline plane: fewer than SUBLANE streams
# run as a block of exactly B rows, which Mosaic must take unpadded.  The
# widths are a 4-shard split of 65,536 events (scatter) and a candidate
# refresh over 4k candidates plus that shard (query).
@pytest.mark.parametrize("kernel", ["scatter", "query", "update"])
def test_one_stream_kernels_compile(one_chip, kernel):
    one = (((1,), jnp.uint32), ((1,), jnp.uint32), ((1,), jnp.int32))
    if kernel == "scatter":
        def fn(keys, vals, seeds, tseeds, lengths):
            return countsketch_scatter_batched(
                keys, vals, ROWS, WIDTH, seeds, p=2.0, scheme="priority",
                transform_seeds=tseeds, lengths=lengths, interpret=False)
        shapes = (((1, 19_072), jnp.int32), ((1, 19_072), jnp.float32),
                  *one)
    elif kernel == "query":
        def fn(tables, qkeys, seeds):
            return countsketch_query_batched(tables, qkeys, seeds,
                                             interpret=False)
        shapes = (((1, ROWS, WIDTH), jnp.float32), ((1, 23_552), jnp.int32),
                  ((1,), jnp.uint32))
    else:
        def fn(vals, seeds, tseeds, lengths, base_keys):
            return countsketch_update_batched(
                vals, ROWS, WIDTH, seeds, p=1.0, transform_seeds=tseeds,
                base_keys=base_keys, lengths=lengths, interpret=False)
        shapes = (((1, EVENTS), jnp.float32), *one, ((1,), jnp.uint32))
    _assert_mosaic(fn, one_chip, *shapes)


def test_transform_compiles(one_chip):
    def transform(keys, vals):
        return ppswor_transform(keys, vals, 1.0, 7, interpret=False)

    n = STREAMS * EVENTS
    _assert_mosaic(transform, one_chip, ((n,), jnp.int32),
                   ((n,), jnp.float32))


# the pipeline plane's device path on a 4-chip host: one stream's shard on
# each chip of the described v5e:2x2, at the stream deployment's widths (a
# 65,536-event flush routed into 4 shards of one common width)
@pytest.fixture(scope="module")
def four_chips(one_chip):
    from jax.experimental import topologies

    from repro.launch.mesh import make_mesh_auto

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    return make_mesh_auto((4,), ("shard",), devices=topo.devices)


def _device_path(mesh):
    from repro import engine as E
    from repro.engine import planes

    cfg = E.EngineConfig(num_streams=4, rows=ROWS, width=WIDTH,
                         candidates=4096, p=2.0, scheme="priority")
    sharding, update, collapse = planes._device_programs(
        E.engine_spec(cfg), mesh, False, True)
    state = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        jax.eval_shape(lambda: E.init_batched(cfg)))
    return sharding, update, collapse, state


@pytest.fixture(scope="module")
def device_path_hlo(four_chips):
    """The compiled HLO text of the SPMD update, each chip's block
    (1, 19,072), and of the collapse."""
    sharding, update, collapse, state = _device_path(four_chips)
    keys = jax.ShapeDtypeStruct((4, 19_072), jnp.int32, sharding=sharding)
    vals = jax.ShapeDtypeStruct((4, 19_072), jnp.float32, sharding=sharding)
    return (update.lower(state, keys, vals).compile().as_text(),
            collapse.lower(state).compile().as_text())


def test_device_path_update_compiles(device_path_hlo):
    """One SPMD program: each chip scatters and refreshes its own shard's
    (1, 19,072) block through the Mosaic kernels inside the shard_map."""
    hlo = device_path_hlo[0]
    assert hlo.count("tpu_custom_call") >= 2     # scatter and query kernels
    assert "collective-permute" not in hlo and "all-reduce" not in hlo


def test_device_path_collapse_compiles(device_path_hlo):
    """The collective all-merge: log2(4) = 2 rounds of collective-permute."""
    hlo = device_path_hlo[1]
    assert "collective-permute" in hlo


def test_collapse_operations_are_told_apart(device_path_hlo):
    """What the benchmark's collapse roofline relies on: the collapse
    program runs no loop or call, so its entry instructions are every
    operation it runs, and none of them shares its name, shape, opcode and
    operands with an instruction of the update program."""
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "chip"))
    import collective_work as cw

    update, collapse = (cw.program_keys(h) for h in device_path_hlo)
    opcodes = {k[2] for k in collapse}
    assert {"collective-permute-start", "collective-permute-done"} <= opcodes
    assert not opcodes & {"while", "conditional", "call"}
    assert update and not update & collapse
