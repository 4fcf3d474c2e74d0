"""The Gram and attention kernels compile for a TPU v5e at the widths of
the benchmark's cells.

Interpret-mode tests (test_kernels.py, test_flash.py) cannot see what the
chip's compiler refuses: blocks not aligned to the (8, 128) tiling, or more
VMEM than a kernel may use.  These tests compile the raw kernels for a
described ``v5e:2x2`` topology, with no chip attached, at the Gram group
shapes of smollm-360m (32 layers, d_model 960, 5 KV heads of 64, d_ff 2560)
and at the attention shapes of the smollm-360m and qwen2.5-14b cells.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every pytest worker imports
this file.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from repro import configs
from repro.core import api
from repro.core.muon import MuonConfig
from repro.kernels import autotune, flash
from repro.models import layers, model_fns
from repro.runtime.elastic import make_mesh
from repro.train.step import init_state, make_train_step
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
def topo():
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
    yield topo
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


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


# (B, S, H, KV, head_dim) of the smollm-360m and qwen2.5-14b cells
ATTENTION_SHAPES = [(4, 2048, 15, 5, 64), (2, 2048, 40, 8, 128)]
MOSAIC = 'custom_call_target="tpu_custom_call"'


def _attend(q, k, v):
    return flash.causal_attention(q, k, v, scale=q.shape[-1] ** -0.5,
                                  interpret=False)


@pytest.mark.parametrize("B,S,H,KV,hd", ATTENTION_SHAPES)
def test_attention_kernels_compile(one_chip, B, S, H, KV, hd):
    shapes = (B, S, H, hd), (B, S, KV, hd), (B, S, KV, hd)
    assert _hlo(_attend, one_chip, *shapes).count(MOSAIC) == 1
    grad = jax.grad(lambda q, k, v: jnp.sum(_attend(q, k, v)),
                    argnums=(0, 1, 2))
    # the forward saving its residuals, dq and dk/dv
    assert _hlo(grad, one_chip, *shapes).count(MOSAIC) == 3


def test_attention_on_a_mesh_keeps_the_chunked_path(topo, one_chip):
    """A Pallas call on a multi-device mesh would need a ``shard_map``: with
    the batch split over the described chips, attention stays in XLA."""
    cfg = layers.AttnConfig(d_model=64, n_heads=6, n_kv_heads=2, head_dim=64)
    p = jax.eval_shape(
        lambda: layers.attention_init(jax.random.PRNGKey(0), cfg))

    def grad_text(sharding, x_sharding):
        ps = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=sharding), p)
        x = jax.ShapeDtypeStruct((4, 2048, 64), jnp.float32,
                                 sharding=x_sharding)
        return jax.jit(jax.grad(lambda p, x: jnp.sum(
            layers.attention(p, cfg, x)[0]))).lower(ps, x).as_text()

    mesh = make_mesh((4, 1), ("data", "model"), topo.devices)
    on_mesh = grad_text(NamedSharding(mesh, PartitionSpec()),
                        NamedSharding(mesh, PartitionSpec("data")))
    assert "tpu_custom_call" not in on_mesh
    assert "tpu_custom_call" in grad_text(one_chip, one_chip)


def test_attention_kernels_are_named_in_the_gradient(one_chip):
    """Each Mosaic call of the compiled step has its ``op_name`` on its own
    line (a trace's per-class split reads instruction lines), under
    ``model.attention`` and ``jvp(``/``transpose(``."""
    cfg = configs.get("smollm-360m", reduced=True, n_layers=2)
    key = jax.random.PRNGKey(0)
    shapes = jax.eval_shape(lambda k: model_fns(cfg).init(cfg, k), key)
    plan = api.dedicate_params(shapes, num_owners=1, strategy="greedy")
    opt = api.Muon(plan, None, config=MuonConfig(mode="owner"))
    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(lambda: init_state(cfg, opt, key)))
    tok = jax.ShapeDtypeStruct((2, 2048), jnp.int32, sharding=one_chip)
    hlo = make_train_step(cfg, opt, None).lower(
        state, {"tokens": tok, "labels": tok}).compile().as_text()
    kernels = [line for line in hlo.splitlines() if MOSAIC in line]
    assert len(kernels) >= 3
    for line in kernels:
        op_name = re.search(r'metadata=\{[^}]*?op_name="([^"]*)"', line)
        assert op_name, line[:200]
        assert "model.attention" in op_name.group(1).split("/")
        assert "jvp(" in op_name.group(1) or "transpose(" in op_name.group(1)
