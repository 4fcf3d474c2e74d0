"""Gram-space Newton-Schulz iteration (paper §3.3, after Zhang et al.).

Write one NS step as ``X_{i+1} = P_i X_i`` with ``P_i = aI + bG_i + cG_i²``
a polynomial in the Gram matrix ``G_i = X_i X_iᵀ``.  Then the Gram matrix
obeys the closed recurrence

    G_{i+1} = P_i G_i P_i                                         (Eq. 4)

and the polar factor is recovered at the end as ``X_k = Q_k X₀`` with
``Q_{i+1} = P_i Q_i``, ``Q₀ = I``.  The iteration stays in the m×m Gram space
instead of the m×n original space, so the dominant cost falls from O(m²n) to
O(m³) whenever m < n.

Key structural fact exploited by the kernels: every matrix appearing in the
iteration (G_i, P_i, Q_i and all their products) is a *polynomial in G₀* —
they are all symmetric and they all commute.  Hence every product below has a
symmetric output and a SYRK-style kernel that computes only the lower triangle
does half the arithmetic (the paper's 48%-share "symmetric Gram kernel").

Operation schedule per step (fp32 accumulation everywhere):

    P  = aI + bG + c·(G@G)     one symmetric product + fused epilogue
    T  = P@G                   symmetric product        (skipped on last step)
    G' = P@T                   symmetric product        (skipped on last step)
    Q' = P@Q                   symmetric product        (Q' := P on first step)

giving ``4k − 3`` m×m symmetric products for k steps, plus one m×n SYRK (G₀)
and one m×n product (final ``Q_k X₀``).

The inner products dispatch either to pure-jnp reference ops or to the Pallas
TPU kernels in ``repro.kernels`` (``use_kernels=True``; compiled on the TPU,
interpreted on the CPU backend; the multi-pod dry-run uses the jnp path — see
docs/DESIGN.md §2 on roofline FLOP accounting).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core.coefficients import Coeffs, get_coefficients
from repro.kernels.ref import bmm

_EPS = 1e-7


@dataclass(frozen=True)
class GramNSConfig:
    """Execution configuration for the Gram NS iteration."""
    num_steps: int = 5
    schedule: str = "polar_express"
    compute_dtype: str = "float32"   # iterate dtype; fp32 accumulation regardless
    use_kernels: bool = False        # Pallas symmetric kernels vs pure jnp
    block_m: int = 128               # kernel block size (autotuner may override)
    block_k: int = 128
    # Owner-local batch chunking (lax.map over sub-batches): bounds the live
    # Gram-space working set for huge shape censuses (1T-class MoE configs).
    # 0 = no chunking.
    owner_chunk: int = 0
    # Fuse the m×m iteration phase across groups sharing a Gram dimension
    # (paper §3.3 shape-batched execution at its widest): one batched
    # recurrence per Gram bucket instead of one per parameter leaf.
    bucket_fusion: bool = False

    def coeffs(self) -> Sequence[Coeffs]:
        return get_coefficients(self.schedule, self.num_steps)


def _ops(cfg: GramNSConfig):
    """Resolve the (syrk, gram_poly, symmul, matmul) op set for ``cfg``."""
    if cfg.use_kernels:
        from repro.kernels import ops as kops
        kw = dict(block_m=cfg.block_m, block_k=cfg.block_k)
        return (
            lambda x: kops.syrk(x, **kw),
            lambda g, a, b, c: kops.gram_poly(g, a, b, c, **kw),
            lambda a, b: kops.symmul(a, b, **kw),
        )
    from repro.kernels import ref as kref
    return kref.syrk_ref, kref.gram_poly_ref, kref.symmul_ref


def gram_newton_schulz(
    m: jax.Array,
    cfg: GramNSConfig = GramNSConfig(),
    *,
    assume_short_fat: bool = False,
) -> jax.Array:
    """Orthogonalize ``m`` of shape ``(..., r, c)`` via Gram-space NS.

    Transposes internally so the Gram side is the smaller dimension unless
    ``assume_short_fat`` asserts r <= c already (the stacked owner-layout path
    pre-transposes groups at plan time, making the whole batch uniform).
    """
    if m.ndim < 2:
        raise ValueError(f"gram_newton_schulz expects a matrix, got {m.shape}")
    out_dtype = m.dtype
    x = m

    transposed = False
    if not assume_short_fat and m.shape[-2] > m.shape[-1]:
        x, transposed = x.mT, True

    # Frobenius norm with fp32 accumulation WITHOUT materializing an fp32
    # copy of x: the square+convert fuse into the reduction.  (An up-front
    # x.astype(f32) gets hoisted by XLA before the owner reshard, doubling
    # the transpose volume of the whole model — see docs/DESIGN.md §9.)
    norm = jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)),
                            axis=(-2, -1), keepdims=True))
    cdtype = jnp.dtype(cfg.compute_dtype)
    x0 = x.astype(cdtype) / (norm + _EPS).astype(cdtype)

    syrk, gram_poly, symmul = _ops(cfg)
    coeffs = cfg.coeffs()

    g = syrk(x0)                                   # G₀ = X₀X₀ᵀ
    q: Optional[jax.Array] = None                  # Q₀ = I, kept implicit
    last = len(coeffs) - 1
    for i, (a, b, c) in enumerate(coeffs):
        p = gram_poly(g, a, b, c)                  # P = aI + bG + c(G@G)
        q = p if q is None else symmul(p, q)       # Q' = P Q
        if i != last:                              # G' not needed after last P
            t = symmul(p, g)                       # T = PG (= GP)
            g = symmul(p, t)                       # G' = PT = P G P

    out = bmm(q, x0)                               # X_k = Q_k X₀
    if transposed:
        out = out.mT
    return out.astype(out_dtype)


def gram_prepare(m: jax.Array, cfg: GramNSConfig):
    """Phase 1: normalize + initial Gram.  m: (..., r, c) with r <= c.
    Returns (x0, G) — G is (..., r, r)."""
    norm = jnp.sqrt(jnp.sum(jnp.square(m.astype(jnp.float32)),
                            axis=(-2, -1), keepdims=True))
    cdtype = jnp.dtype(cfg.compute_dtype)
    x0 = m.astype(cdtype) / (norm + _EPS).astype(cdtype)
    syrk, _, _ = _ops(cfg)
    return x0, syrk(x0)


def gram_iterate(g: jax.Array, cfg: GramNSConfig) -> jax.Array:
    """Phase 2: the m×m Gram recurrence; returns the polar accumulator Q_k.
    This phase is shape-uniform in the Gram dimension only, so stacks from
    different (m, n) groups with equal m are batched together here — the
    bucket fusion of the paper's shape-batched execution."""
    _, gram_poly, symmul = _ops(cfg)
    coeffs = cfg.coeffs()
    q = None
    last = len(coeffs) - 1
    for i, (a, b, c) in enumerate(coeffs):
        p = gram_poly(g, a, b, c)
        q = p if q is None else symmul(p, q)
        if i != last:
            t = symmul(p, g)
            g = symmul(p, t)
    return q


def gram_finish(q: jax.Array, x0: jax.Array, out_dtype) -> jax.Array:
    """Phase 3: X_k = Q_k X₀."""
    return bmm(q, x0).astype(out_dtype)


def gram_ns_flops(m: int, n: int, num_steps: int = 5, batch: int = 1,
                  symmetric_kernels: bool = True) -> dict:
    """Analytic FLOP model (per §Roofline kernel adjustment & load balancer).

    Returns both the naive-GEMM count (what XLA's cost_analysis sees on the
    jnp path) and the symmetric-kernel-adjusted count (what the Pallas path
    executes on TPU: every m×m product computes only the lower triangle).
    """
    if m > n:
        m, n = n, m
    sym_products = 4 * num_steps - 3
    mm = 2.0 * m * m * m                 # one full m×m×m GEMM
    rect = 2.0 * m * m * n               # one m×m @ m×n GEMM (or SYRK of X)
    full = batch * (rect                 # G₀ = X X ᵀ
                    + sym_products * mm  # Gram-space products
                    + rect)              # Q_k X₀
    half = batch * (rect / 2.0 + sym_products * mm / 2.0 + rect)
    ns_standard = batch * num_steps * (2.0 * rect + mm)
    return {
        "gram_full_gemm": full,
        "gram_symmetric_kernel": half if symmetric_kernels else full,
        "standard_ns": ns_standard,
    }
