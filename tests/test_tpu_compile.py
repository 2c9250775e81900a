"""The fused JRBA congestion kernel compiles for a TPU v5e.

The TPU compiler runs here without a chip: it compiles for a described
``v5e:2x2`` topology and refuses what the chip's Mosaic compiler would refuse
(unaligned blocks, shape casts across the lane axis, unsupported ops), which
interpret mode never checks. The shapes are solver buckets ``(B, Nf, K,
La_pad)`` that ``chip_smoke.py``'s phases produce: the smallest B == 1
bucket, a batched bucket whose ``La_pad`` is a link count capped at L
(edge-mesh, L = 21), and the widest active-link bucket (wan-mesh-xl).
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.jrba_congestion import sparse_congestion_solve

SHAPES = {
    "smallest": (1, 8, 4, 8),
    "batched": (128, 16, 4, 21),
    "widest": (1, 64, 4, 128),
}


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2, with the persistent compilation
    cache off (an entry compiled for a described chip cannot be read back
    without one)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    enabled = jax.config.jax_enable_compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            try:
                topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
            except Exception as e:
                pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


@pytest.mark.parametrize("shape", SHAPES.values(), ids=SHAPES.keys())
def test_congestion_kernel_compiles_for_v5e(one_chip, shape):
    B, Nf, K, La = shape

    def spec(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    compiled = sparse_congestion_solve.lower(
        spec((B, Nf, K, La), jnp.float32),
        spec((B, Nf, K), jnp.bool_),
        spec((B, Nf), jnp.float32),
        spec((B, La), jnp.float32),
        spec((B,), jnp.float32),
        n_iters=400,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
