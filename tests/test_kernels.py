"""Per-kernel validation: Pallas (interpreted on the CPU backend) vs pure-jnp
oracles.

Sweeps shapes (aligned / unaligned / tiny / rectangular), dtypes, and block
sizes, asserting allclose against ref.py per the deliverable spec.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.symmul import tri_index_tables

SHAPES_SQUARE = [(1, 16), (2, 64), (3, 128), (2, 160), (1, 200), (4, 96)]
SHAPES_RECT = [(1, 16, 64), (2, 64, 256), (2, 96, 40), (1, 128, 384),
               (3, 32, 32), (1, 200, 72)]
DTYPES = [jnp.float32, jnp.bfloat16]
BLOCKS = [(64, 64), (128, 128), (128, 64)]


def _sym(shape, seed, dtype):
    a = jax.random.normal(jax.random.PRNGKey(seed), shape, dtype=jnp.float32)
    return ((a + a.mT) / 2).astype(dtype)


def _tol(dtype):
    # blocked accumulation order differs from XLA's dot — allow small noise
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("batch,m", SHAPES_SQUARE)
@pytest.mark.parametrize("dtype", DTYPES)
def test_symmul_matches_ref(batch, m, dtype):
    a = _sym((batch, m, m), 0, dtype)
    b = _sym((batch, m, m), 1, dtype)
    # commuting not required for C = A@B correctness of the raw product —
    # the kernel computes the true lower blocks; mirror assumes symmetry, so
    # use powers of one matrix (guaranteed symmetric product).
    b = ref.symmul_ref(a, a)  # A² is symmetric; A and A² commute
    got = ops.symmul(a, b, block_m=64, block_k=64)
    want = ref.symmul_ref(a.astype(jnp.float32), b.astype(jnp.float32))
    # In finite precision A·B is only *approximately* symmetric (quantized B
    # no longer exactly commutes with A); the kernel mirrors the lower
    # triangle, i.e. symmetrizes.  Compare against the symmetrized reference.
    want = ref.mirror_lower(want)
    got = ref.mirror_lower(jnp.asarray(got, jnp.float32))
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                               **_tol(dtype))


@pytest.mark.parametrize("batch,m,n", SHAPES_RECT)
@pytest.mark.parametrize("dtype", DTYPES)
def test_syrk_matches_ref(batch, m, n, dtype):
    x = jax.random.normal(jax.random.PRNGKey(2), (batch, m, n), dtype=jnp.float32)
    x = x.astype(dtype)
    got = ops.syrk(x, block_m=64, block_k=64)
    want = ref.syrk_ref(x.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                               **_tol(dtype))


@pytest.mark.parametrize("m", [32, 128, 160])
@pytest.mark.parametrize("coeffs", [(3.4445, -4.775, 2.0315), (8.287, -23.6, 17.3)])
def test_gram_poly_fused_epilogue(m, coeffs):
    g = _sym((2, m, m), 4, jnp.float32)
    a, b, c = coeffs
    got = ops.gram_poly(g, a, b, c, block_m=64, block_k=64)
    want = ref.gram_poly_ref(g, a, b, c)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("bm,bk", BLOCKS)
def test_block_size_invariance(bm, bk):
    a = _sym((2, 256, 256), 5, jnp.float32)
    want = ref.symmul_ref(a, a)
    got = ops.symmul(a, a, block_m=bm, block_k=bk)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_unaligned_padding_roundtrip():
    """Shapes not divisible by the block size must still be exact."""
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 100, 212))
    got = ops.syrk(x, block_m=64, block_k=64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref.syrk_ref(x)),
                               rtol=1e-5, atol=1e-5)


def test_tri_index_tables():
    ii, jj = tri_index_tables(4)
    assert len(ii) == 10
    assert all(j <= i for i, j in zip(ii, jj))
    # covers exactly the lower triangle
    assert sorted(zip(ii.tolist(), jj.tolist())) == \
        [(i, j) for i in range(4) for j in range(i + 1)]


def test_mirror_lower():
    raw = jnp.arange(16.0).reshape(1, 4, 4) + jnp.triu(
        jnp.full((4, 4), jnp.nan), 1)  # garbage above diagonal
    out = np.asarray(ref.mirror_lower(raw))
    assert not np.isnan(out).any()
    np.testing.assert_allclose(out, out.transpose(0, 2, 1))


def test_gram_ns_end_to_end_with_kernels():
    """Full Gram NS through the Pallas path == jnp path == standard NS."""
    from repro.core.gram_ns import GramNSConfig, gram_newton_schulz
    from repro.core.newton_schulz import newton_schulz
    m = jax.random.normal(jax.random.PRNGKey(7), (3, 64, 192))
    cfg_k = GramNSConfig(num_steps=5, use_kernels=True,
                         block_m=64, block_k=64)
    cfg_j = GramNSConfig(num_steps=5)
    got_k = gram_newton_schulz(m, cfg_k, assume_short_fat=True)
    got_j = gram_newton_schulz(m, cfg_j, assume_short_fat=True)
    want = newton_schulz(m, num_steps=5)
    np.testing.assert_allclose(np.asarray(got_k), np.asarray(got_j),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(got_k), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_autotune_cache_roundtrip(tmp_path):
    from repro.kernels import autotune
    autotune.clear_memory_cache()
    path = str(tmp_path / "cache.json")
    bm, bk = autotune.tune("symmul", 512, 512, "float32",
                           backend="analytical", cache_path=path)
    assert bm % 8 == 0 and bk % 8 == 0
    # second lookup is a pure cache hit (same result, file persisted)
    assert autotune.lookup("symmul", 512, 512, "float32", cache_path=path) == (bm, bk)
    autotune.clear_memory_cache()
    assert autotune.lookup("symmul", 512, 512, "float32", cache_path=path) == (bm, bk)
    autotune.clear_memory_cache()


def test_autotune_candidates_respect_vmem():
    from repro.kernels import autotune
    for bm, bk in autotune.candidate_blocks(2048, 2048, 4):
        ws = (2 * (bm * bk + bk * bm) + 2 * bm * bm) * 4
        assert ws <= autotune._VMEM_BYTES * autotune._VMEM_FRACTION
