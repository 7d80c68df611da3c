"""The encode kernels compile for a TPU v5e described without the chip.

Interpret mode (the rest of the suite) cannot see what the TPU compiler
refuses: unaligned blocks, scalar stores to vector memory, layouts that
do not match XLA's.  Each case here lowers and compiles one kernel for
one v5e chip at an aligned and an unaligned flush size, and checks that
the compiled program holds the Mosaic kernel.  Nothing runs.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.delta_encode import ops as de
from repro.kernels.grammar_stats import ops as gs

SIZES = (65536, 65536 + 77)

# kernel -> (jitted call, argument shapes for n elements)
KERNELS = {
    "delta_zigzag": (de.delta_zigzag, lambda n: [((n,), jnp.uint32)]),
    "delta_zigzag_varint": (de.delta_zigzag_varint,
                            lambda n: [((n,), jnp.uint32)]),
    "uvarint_encode64": (de.uvarint_encode64,
                         lambda n: [((n,), jnp.uint32)] * 2),
    "row_boundaries": (gs.row_boundaries, lambda n: [((n, 3), jnp.int32)]),
    "histogram": (lambda s: gs.histogram(s, 5000),
                  lambda n: [((n,), jnp.int32)]),
    "digram_codes": (lambda s: gs.digram_codes(s, 1000),
                     lambda n: [((n,), jnp.int32)]),
}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip; keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("n", SIZES)
@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, n, one_chip, no_persistent_cache):
    fn, shapes = KERNELS[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes(n)]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
