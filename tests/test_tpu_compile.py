"""The main path's device programs compile for a TPU v5e that is described,
not attached (the `on-chip-measurement` guide, section 2): the headline
fused step and the device-views step at the bench shape, the aggregate
step, and the 4-way data-parallel step on a ``v5e:2x2`` mesh.  What the
chip's compiler refuses (layout, memory, partitioning) fails here, at no
chip time.  Nothing runs, so these say nothing about results or speed.

The topology is described inside a module fixture, never at import: only
one process at a time may load libtpu, and every xdist worker imports
every test file.
"""
import numpy as np
import pytest

from logparser_tpu.tools.demolog import HEADLINE_FIELDS

B, L = 65536, 384
# The aggregate step's compile time grows with B (on this CPU: 5.7 s at
# 4,096, 26 s at 16,384, 87 s at 65,536), so it compiles at 4,096 here.
B_AGG = 4096
# v5e: 16 GB of HBM per chip.
HBM_BYTES = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu / no v5e support
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    # A described-chip compile is written to JAX's persistent cache but
    # cannot be read back without the chip: keep it out of the cache.
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def parser():
    from logparser_tpu.tpu.batch import TpuBatchParser

    return TpuBatchParser("combined", HEADLINE_FIELDS)


def _args(sharding=None, b=B):
    import jax
    import jax.numpy as jnp

    kw = {} if sharding is None else {"sharding": sharding}
    return (jax.ShapeDtypeStruct((b, L), jnp.uint8, **kw),
            jax.ShapeDtypeStruct((b,), jnp.int32, **kw))


def _fits(compiled, budget=HBM_BYTES):
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert 0 < total < budget, total
    return total


@pytest.mark.parametrize("executor", ["plain", "views"])
def test_parse_step_compiles_for_one_v5e_chip(parser, one_chip, executor):
    fn = parser.device_fn() if executor == "plain" else parser.device_views_fn()
    compiled = fn._jit.lower(*_args(one_chip)).compile()
    _fits(compiled)
    assert compiled.as_text()  # a TPU executable, not a CPU fallback


def test_aggregate_step_compiles_for_one_v5e_chip(parser, one_chip):
    import jax
    import jax.numpy as jnp

    spec = parser._resolve_agg_spec(
        [{"op": "count_by", "field": "STRING:request.status.last"}])
    fn = parser._agg_executor(spec)
    compiled = fn.lower(
        *_args(one_chip, B_AGG),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((B_AGG,), jnp.bool_, sharding=one_chip),
    ).compile()
    _fits(compiled)


def test_data_parallel_step_compiles_for_v5e_2x2(parser, topo):
    from logparser_tpu.parallel.mesh import make_mesh
    from logparser_tpu.tpu.pipeline import build_units_jnp_fn

    mesh = make_mesh(n_data=4, devices=topo.devices)
    compiled = build_units_jnp_fn(parser.units, mesh=mesh).lower(
        *_args()).compile()
    # Per device: a quarter of the batch.
    _fits(compiled)
    text = compiled.as_text()
    # Lines are independent: the partitioned step needs no collective.
    for op in ("all-reduce", "all-gather", "all-to-all",
               "collective-permute"):
        assert op not in text, op
    assert np.prod(mesh.devices.shape) == 4
