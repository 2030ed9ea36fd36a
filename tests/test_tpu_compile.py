"""Compile the fused phase-1 kernels for a TPU v5e that is described, not
attached, at the 1-chip shares of the benchmark's deployments: the paper's
Wikipedia, 1,045,376 docs x 400 LSA features (800 code columns under the
combined encoder) and a 128-query batch, and the eighth of a 768-d MS MARCO
passage index, 1,105,280 docs x 768 features (1,536 columns) and a 64-query
batch; page 320.  Interpret mode hides what Mosaic refuses (an in-kernel
sort or top_k, an unsupported shape cast, a misaligned block); this compile
does not.  No chip is needed and nothing runs.

The topology is described inside a fixture, never while the module is
imported: only one process at a time may load the TPU compiler's library.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.fused_phase1.kernel import (fused_phase1_pallas,
                                               fused_phase1_quant_pallas)

PAGE = 320
# (docs, features, queries) per chip of each deployment; 2 code columns
# per feature
SHAPES = {"wiki-1chip": (1_045_376, 400, 128),
          "msmarco768-1chip": (1_105_280, 768, 64)}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_fused_fp32_kernel_compiles_for_v5e(one_chip, shape):
    D, n_features, Q = SHAPES[shape]
    C = 2 * n_features
    S = lambda shape, dt: _spec(shape, dt, one_chip)
    compiled = fused_phase1_pallas.lower(
        S((D, C), jnp.int8), S((Q, C), jnp.int8), S((Q, C), jnp.float32),
        S((D,), jnp.bool_), page=PAGE).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_fused_int8_kernel_compiles_for_v5e(one_chip, shape):
    D, n_features, Q = SHAPES[shape]
    S = lambda shape, dt: _spec(shape, dt, one_chip)
    compiled = fused_phase1_quant_pallas.lower(
        S((D, n_features), jnp.int8), S((D,), jnp.float32),
        S((D,), jnp.float32), S((Q, n_features), jnp.float32),
        S((Q, 1), jnp.float32), S((D,), jnp.bool_), page=PAGE).compile()
    assert "tpu_custom_call" in compiled.as_text()
