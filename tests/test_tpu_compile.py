"""Compile the served path's device programs for one TPU v5e chip.

No chip is attached: the TPU compiler builds each program for a described
``v5e:2x2`` topology, which catches what interpret mode cannot — Mosaic
layout and tiling refusals, scoped-VMEM overflows, programs too large for
the chip's 16 GB of HBM.  Nothing runs, so these tests say nothing about
results or speed.

The topology is described inside a module-scoped fixture (never at import):
describing it loads the TPU library, which one process at a time may hold.
The persistent compilation cache is off around these compiles — an entry
written for a described chip cannot be read back without one.
"""

import pytest

jax = pytest.importorskip("jax")
jnp = jax.numpy

from repro.core import engine_jax  # noqa: E402  (enables x64, as served)
from repro.kernels import expand_fused, segsum  # noqa: E402

V5E_HBM = 16 * 10**9
T_PAD = 1 << 26        # the output bucket of the 38.3M-row lastfm_A1 join


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler, or its library is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    """Compile ``fn`` for the described chip; check it fits the HBM."""
    compiled = jax.jit(fn).lower(*shapes).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < V5E_HBM, f"{used} bytes do not fit one v5e chip"
    return compiled


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("k", [4, 7])
def test_expand_gather_many_compiles(one_chip, k):
    runs = 1 << 20
    compiled = _compile(
        lambda p, b: expand_fused.expand_gather_many(p, b, t_pad=T_PAD),
        _spec(one_chip, (k, runs), jnp.int32),
        _spec(one_chip, (runs,), jnp.int32))
    assert "tpu_custom_call" in compiled.as_text()


def test_mul_segsum_compiles(one_chip):
    n = 1 << 20
    compiled = _compile(
        lambda s, x, y: segsum.mul_segsum(s, x, y, num_segments=n),
        _spec(one_chip, (n,), jnp.int32),
        _spec(one_chip, (n,), jnp.float32),
        _spec(one_chip, (n,), jnp.float32))
    assert "tpu_custom_call" in compiled.as_text()


def test_frontier_lookup_compiles(one_chip):
    n, groups = 1 << 16, 1 << 12
    _compile(
        lambda pc, live, keys, start, count: engine_jax._frontier_lookup(
            pc, live, keys, start, count, radices=(1892, 17632)),
        _spec(one_chip, (2, n), jnp.int32),
        _spec(one_chip, (), jnp.int32),
        _spec(one_chip, (groups,), jnp.int64),
        _spec(one_chip, (groups,), jnp.int32),
        _spec(one_chip, (groups,), jnp.int32))


def test_sorted_runs_compiles(one_chip):
    # the int64 sort's compile time grows steeply with the length (about a
    # minute from 2**15 up); a short bucket lowers the same operations
    _compile(engine_jax._sorted_runs,
             _spec(one_chip, (1 << 12,), jnp.int64))

