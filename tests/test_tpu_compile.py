"""The Gram kernels compile for a TPU v5e at smollm-360m's widths.

Interpret-mode tests (test_kernels.py) cannot see what the chip's compiler
refuses: blocks not aligned to the (8, 128) tiling, or more VMEM than a
kernel may use.  These tests compile the raw kernels for a described
``v5e:2x2`` topology, with no chip attached, at the Gram group shapes of
smollm-360m (32 layers, d_model 960, 5 KV heads of 64, d_ff 2560).

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every pytest worker imports
this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import autotune
from repro.kernels.gram_syrk import syrk_lower
from repro.kernels.symmul import symmul_lower

STACK = 32                                  # one matrix per layer
SYRK_SHAPES = [(960, 2560), (320, 960), (960, 960)]     # (m, n) groups
GRAM_DIMS = [960, 320]                      # their Gram sides m
# the analytic scorer's picks at these shapes: 512x512 for the 960-wide
# groups, 128x1024 for the 320x960 SYRK, 128x128 (also GramNSConfig's
# default) for the 320-wide Gram
BLOCKS = [(512, 512), (128, 1024), (128, 128)]


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # Compiles for a described chip can be written to the persistent cache
    # but not read back without one; keep the cache out of these tests.
    from jax.experimental.compilation_cache import compilation_cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _hlo(fn, one_chip, *shapes, **kw):
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in shapes]
    return jax.jit(lambda *a: fn(*a, **kw)).lower(*args).compile().as_text()


@pytest.mark.parametrize("m,n", SYRK_SHAPES)
@pytest.mark.parametrize("bm,bk", BLOCKS)
def test_syrk_compiles(one_chip, m, n, bm, bk):
    hlo = _hlo(syrk_lower, one_chip, (STACK, m, n), block_m=bm, block_k=bk)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("m", GRAM_DIMS)
@pytest.mark.parametrize("epilogue", ["plain", "gram_poly"])
@pytest.mark.parametrize("bm,bk", BLOCKS)
def test_symmul_compiles(one_chip, m, epilogue, bm, bk):
    coeffs = (3.4445, -4.775, 2.0315) if epilogue == "gram_poly" else None
    hlo = _hlo(symmul_lower, one_chip, (STACK, m, m), (STACK, m, m),
               epilogue=epilogue, coeffs=coeffs, block_m=bm, block_k=bk)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("bm,bk", autotune.candidate_blocks(960, 960, 4))
def test_candidate_blocks_compile_gram_poly(one_chip, bm, bk):
    """Whatever the autotuner may pick for a 960-wide Gram compiles."""
    hlo = _hlo(symmul_lower, one_chip, (STACK, 960, 960), (STACK, 960, 960),
               epilogue="gram_poly", coeffs=(3.4445, -4.775, 2.0315),
               block_m=bm, block_k=bk)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("bm,bk", autotune.candidate_blocks(960, 2560, 4))
def test_candidate_blocks_compile_syrk(one_chip, bm, bk):
    hlo = _hlo(syrk_lower, one_chip, (STACK, 960, 2560), block_m=bm,
               block_k=bk)
    assert "tpu_custom_call" in hlo
