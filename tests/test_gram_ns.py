"""Gram Newton-Schulz correctness: agreement with standard NS and SVD oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.coefficients import POLAR_EXPRESS, get_coefficients
from repro.core.gram_ns import GramNSConfig, gram_newton_schulz, gram_ns_flops
from repro.core.newton_schulz import msign_svd, newton_schulz

jax.config.update("jax_enable_x64", False)


def _rand(shape, seed=0, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, dtype=dtype)


@pytest.mark.parametrize("shape", [(16, 16), (16, 64), (64, 16), (48, 80),
                                   (8, 256), (100, 36)])
@pytest.mark.parametrize("schedule", ["polar_express", "quintic"])
def test_gram_matches_standard_ns(shape, schedule):
    m = _rand(shape)
    ref = newton_schulz(m, num_steps=5, schedule=schedule)
    got = gram_newton_schulz(m, GramNSConfig(num_steps=5, schedule=schedule))
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("shape", [(32, 32), (24, 96), (96, 24)])
def test_ns_approximates_polar_factor(shape):
    m = _rand(shape, seed=3)
    exact = msign_svd(m)
    for fn in (lambda x: newton_schulz(x, num_steps=8),
               lambda x: gram_newton_schulz(x, GramNSConfig(num_steps=8))):
        got = fn(m)
        np.testing.assert_allclose(np.asarray(got), np.asarray(exact),
                                   rtol=0, atol=5e-2)


def test_singular_values_driven_to_one():
    m = _rand((40, 120), seed=7)
    out = gram_newton_schulz(m, GramNSConfig(num_steps=8))
    s = jnp.linalg.svd(out.astype(jnp.float32), compute_uv=False)
    assert float(jnp.max(jnp.abs(s - 1.0))) < 5e-2


def test_batched_matches_loop():
    stack = _rand((6, 24, 48), seed=1)
    cfg = GramNSConfig(num_steps=5)
    batched = gram_newton_schulz(stack, cfg, assume_short_fat=True)
    for i in range(stack.shape[0]):
        single = gram_newton_schulz(stack[i], cfg)
        np.testing.assert_allclose(np.asarray(batched[i]), np.asarray(single),
                                   rtol=1e-4, atol=1e-4)


def test_orthogonality_of_output():
    m = _rand((32, 128), seed=11)
    o = gram_newton_schulz(m, GramNSConfig(num_steps=8))
    gram = np.asarray(o @ o.T)
    np.testing.assert_allclose(gram, np.eye(32), atol=8e-2)


def test_bf16_input_supported():
    m = _rand((32, 64), seed=5).astype(jnp.bfloat16)
    out = gram_newton_schulz(m, GramNSConfig(num_steps=5))
    assert out.dtype == jnp.bfloat16
    ref = newton_schulz(m.astype(jnp.float32), num_steps=5)
    np.testing.assert_allclose(np.asarray(out, dtype=np.float32),
                               np.asarray(ref), atol=5e-2)


def test_every_gram_product_at_highest_precision():
    """The Gram recurrence diverges when its products round to bf16 (the
    TPU's default for fp32 matmuls) on near-low-rank momentum, so every
    product it issues must ask for HIGHEST whatever the ambient default."""
    m = _rand((4, 32, 96), seed=2)
    with jax.default_matmul_precision("bfloat16"):
        jaxpr = jax.make_jaxpr(lambda x: gram_newton_schulz(
            x, GramNSConfig(num_steps=5), assume_short_fat=True))(m)
    dots = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "dot_general"]
    assert len(dots) == 4 * 5 - 3 + 2      # symmetric products + G₀ + Q·X₀
    for e in dots:
        assert e.params["precision"] == (jax.lax.Precision.HIGHEST,) * 2


def test_coefficient_schedules():
    sched = get_coefficients("polar_express", 10)
    assert len(sched) == 10
    assert sched[:8] == POLAR_EXPRESS
    assert sched[9] == POLAR_EXPRESS[-1]
    q = get_coefficients("quintic", 5)
    assert all(c == (3.4445, -4.7750, 2.0315) for c in q)
    with pytest.raises(ValueError):
        get_coefficients("nope", 5)


def test_flop_model_sane():
    f = gram_ns_flops(1024, 4096, num_steps=5, batch=2)
    # Gram-space must beat standard NS for fat matrices, symmetric halves it.
    assert f["gram_full_gemm"] < f["standard_ns"]
    assert f["gram_symmetric_kernel"] < f["gram_full_gemm"]
    # At square shapes Gram-space only wins WITH the symmetric kernels
    # (11.5 vs 15 m³-units) — full-GEMM Gram is more FLOPs than standard NS.
    sq = gram_ns_flops(512, 512)
    assert sq["gram_symmetric_kernel"] < sq["standard_ns"] < sq["gram_full_gemm"]
