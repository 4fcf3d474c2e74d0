"""Pallas TPU kernel: batched SYRK, G = X Xᵀ, lower-triangle only.

Computes the initial Gram matrix of the Gram Newton-Schulz iteration for a
stack of matrices X of shape (B, m, n) (owner-local slice of a shape group).
G is symmetric by construction, so only blocks (i, j) with j <= i are
computed — the mainloop does half the arithmetic of a general batched GEMM
and the epilogue mirror (ops.py / ref.mirror_lower) reconstructs the dense
output required by the subsequent Gram NS steps (paper §3.3).

Structure mirrors ``symmul.py``: triangular grid via scalar-prefetched (i, j)
tables, fp32 VMEM scratch accumulation at HIGHEST precision, MXU-aligned
autotuned block shapes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.symmul import tri_index_tables


def _syrk_kernel(idx_i, idx_j, xi_ref, xj_ref, o_ref, acc_ref, *, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        xi_ref[0], xj_ref[0],
        dimension_numbers=(((1,), (1,)), ((), ())),  # X_i · X_jᵀ
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _done():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def _pad(x: jax.Array, axis: int, mult: int) -> jax.Array:
    rem = (-x.shape[axis]) % mult
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, rem)
    return jnp.pad(x, pad)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_k", "interpret", "out_dtype"))
def syrk_lower(
    x: jax.Array,
    *,
    block_m: int = 128,
    block_k: int = 128,
    interpret: bool = False,
    out_dtype=None,
) -> jax.Array:
    """Raw lower-triangle G = X Xᵀ for x of shape (B, m, n).

    Returns (B, m, m) with the strict upper triangle UNWRITTEN — callers must
    ``ref.mirror_lower``.
    """
    if x.ndim != 3:
        raise ValueError(f"expected (B, m, n), got {x.shape}")
    batch, m, n = x.shape
    out_dtype = out_dtype or x.dtype
    bm = min(block_m, m)
    bk = min(block_k, n)

    x_p = _pad(_pad(x, 1, bm), 2, bk)
    mp, np_ = x_p.shape[1], x_p.shape[2]
    nb, nk = mp // bm, np_ // bk
    ii, jj = tri_index_tables(nb)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(batch, len(ii), nk),
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda bi, l, k, ii, jj: (bi, ii[l], k)),
            pl.BlockSpec((1, bm, bk), lambda bi, l, k, ii, jj: (bi, jj[l], k)),
        ],
        out_specs=pl.BlockSpec(
            (1, bm, bm), lambda bi, l, k, ii, jj: (bi, ii[l], jj[l])),
        scratch_shapes=[pltpu.VMEM((bm, bm), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_syrk_kernel, nk=nk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((batch, mp, mp), out_dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        name="gram_syrk",
    )(jnp.asarray(ii), jnp.asarray(jj), x_p, x_p)
    return out[:, :m, :m]
