"""Pure-jnp oracles for the Pallas kernels.

These are the semantic references the kernel tests ``assert_allclose``
against, and also the execution path used on CPU and in the multi-pod
dry-run (Pallas interpret mode unrolls the grid into enormous HLO, so the
dry-run lowers this path and the roofline harness applies the analytic
symmetric-kernel FLOP adjustment — see docs/DESIGN.md §2).

All functions accept arbitrary leading batch dims and accumulate in fp32.
Every product runs at HIGHEST precision, i.e. exact fp32 on the TPU's MXU.
At the TPU's default precision an fp32 matmul rounds its operands to bf16,
and the Gram recurrence does not survive that: rounding pushes the small
eigenvalues of a near-low-rank momentum (early training) below zero, where
the NS polynomial grows them without bound — updates of order 1e12 on a
four-chip smollm-360m run.  Standard NS on X is stable under the same
rounding.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def bmm(a: jax.Array, b: jax.Array) -> jax.Array:
    """Batched (…, m, k) @ (…, k, n), fp32 accumulation, HIGHEST precision."""
    out = jax.lax.dot_general(
        a, b,
        dimension_numbers=(((a.ndim - 1,), (b.ndim - 2,)),
                           (tuple(range(a.ndim - 2)), tuple(range(b.ndim - 2)))),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    return out.astype(a.dtype)


def syrk_ref(x: jax.Array) -> jax.Array:
    """G = X Xᵀ for X of shape (..., m, n); output (..., m, m), symmetric."""
    return bmm(x, x.mT)


def symmul_ref(a: jax.Array, b: jax.Array) -> jax.Array:
    """C = A B for symmetric commuting A, B (C symmetric). Shapes (..., m, m)."""
    return bmm(a, b)


def gram_poly_ref(g: jax.Array, a: float, b: float, c: float) -> jax.Array:
    """P = aI + bG + c(G@G) for symmetric G of shape (..., m, m)."""
    m = g.shape[-1]
    eye = jnp.eye(m, dtype=g.dtype)
    return (a * eye + b * g + c * bmm(g, g)).astype(g.dtype)


def mirror_lower(c_raw: jax.Array) -> jax.Array:
    """Reconstruct a full symmetric matrix from block-lower-triangular output.

    The Pallas kernels write only blocks (i, j) with j <= i; everything
    strictly above the diagonal is unwritten garbage.  ``tril`` discards it
    and the strict lower triangle is mirrored up.
    """
    lower = jnp.tril(c_raw)
    return lower + jnp.tril(c_raw, -1).mT
