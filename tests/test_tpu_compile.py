"""The main path's Pallas kernels compiled for a described TPU v5e.

No chip is needed: the TPU compiler compiles for a topology that is
described, not attached, and refuses what the chip would refuse (tiles not
aligned to the layout, too much VMEM). Interpret-mode tests cannot see
that. Each case must lower to a Mosaic kernel (``tpu_custom_call``), not
to the Pallas interpreter.

Shapes are the ones separation and message passing use on the
``grid_instance(1024, 1024)`` solve of ``chip_smoke.py``: the short
(width-16) and full row-cap (width-128) buckets of ``cycle_intersect``,
and ``triangle_mp`` sweeps. The topology is described inside a fixture,
never at import: only one process may load the TPU library at a time.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.cycle_intersect.kernel import intersect_rows_pallas
from repro.kernels.triangle_mp.kernel import mp_sweep_pallas


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a described-chip compile is written to the persistent cache but
        # cannot be read back without the chip; keep the cache out of it
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("rows", [256, 2048])
def test_triangle_mp_compiles_to_mosaic(one_chip, rows):
    x = _spec((rows, 128), jnp.float32, one_chip)
    compiled = mp_sweep_pallas.lower(x, x, x, block_rows=256,
                                     interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("R,W,Wj", [
    (1024, 16, 16), (256, 16, 16), (1024, 4, 16),        # short bucket
    (128, 128, 128), (32, 128, 128), (128, 4, 128),      # full row cap
])
def test_cycle_intersect_compiles_to_mosaic(one_chip, R, W, Wj):
    ci = _spec((R, W), jnp.int32, one_chip)
    cj = _spec((R, Wj), jnp.int32, one_chip)
    compiled = intersect_rows_pallas.lower(ci, cj, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
