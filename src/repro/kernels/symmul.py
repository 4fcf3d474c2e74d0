"""Pallas TPU kernel: batched symmetric-output matrix multiply.

The Gram Newton-Schulz iteration (core/gram_ns.py) multiplies matrices that
are all polynomials in the initial Gram matrix G₀ — they commute and every
product is symmetric.  This kernel therefore computes **only the block-lower
triangle** of C = A @ B (paper §3.3, "SYRK-style execution path"): the grid
enumerates the ``nb(nb+1)/2`` lower blocks instead of all ``nb²``, nearly
halving both MXU work and output traffic.  The strict upper triangle of the
raw output is unwritten; ``ops.py`` mirrors it (``ref.mirror_lower``).

Two fused epilogue modes (selected statically):

* ``plain``      — C_raw[i,j] = acc
* ``gram_poly``  — C_raw[i,j] = a·I[i,j] + b·G[i,j] + c·acc, computing
  P = aI + bG + cG² directly from the G@G pass, so the polynomial
  evaluation never round-trips HBM (paper: "elementwise operations …
  fused into the same epilogue").

Layout notes (TPU):
  * block shapes are MXU-aligned multiples of 128 chosen by the autotuner
    under a VMEM budget (see kernels/autotune.py);
  * the (i, j) block coordinates of the triangular grid are delivered via
    scalar prefetch (host-precomputed int32 tables) so the index maps stay
    scalar-core friendly;
  * accumulation is fp32 in VMEM scratch regardless of the operand dtype,
    and every product asks for HIGHEST precision, as ``ref.py`` does (the
    Gram recurrence diverges when its products round to bf16).

Validated in interpret mode on the CPU backend against ``ref.py``
(tests/test_kernels.py); compiled for a v5e in tests/test_tpu_compile.py.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HIGHEST = jax.lax.Precision.HIGHEST


def tri_index_tables(n_blocks: int) -> tuple[np.ndarray, np.ndarray]:
    """Host-side (i, j) coordinates of the block-lower triangle, row-major."""
    ii, jj = [], []
    for i in range(n_blocks):
        for j in range(i + 1):
            ii.append(i)
            jj.append(j)
    return (np.asarray(ii, dtype=np.int32), np.asarray(jj, dtype=np.int32))


def _plain_kernel(idx_i, idx_j, a_ref, b_ref, o_ref, acc_ref, *, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[0], b_ref[0], precision=_HIGHEST,
                            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _done():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def _gram_poly_kernel(idx_i, idx_j, a_ref, b_ref, g_ref, o_ref, acc_ref, *,
                      nk: int, bm: int, coeffs):
    k = pl.program_id(2)
    l = pl.program_id(1)  # hoisted: program_id is not legal inside pl.when

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[0], b_ref[0], precision=_HIGHEST,
                            preferred_element_type=jnp.float32)

    a_c, b_c, c_c = coeffs
    bi, bj = idx_i[l], idx_j[l]
    rows = bi * bm + jax.lax.broadcasted_iota(jnp.int32, (bm, bm), 0)
    cols = bj * bm + jax.lax.broadcasted_iota(jnp.int32, (bm, bm), 1)
    eye = (rows == cols).astype(jnp.float32)

    @pl.when(k == nk - 1)
    def _done():
        acc = a_c * eye + b_c * g_ref[0].astype(jnp.float32) + c_c * acc_ref[...]
        o_ref[0] = acc.astype(o_ref.dtype)


def _pad_square(x: jax.Array, size: int) -> jax.Array:
    m = x.shape[-1]
    if m == size:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 2) + [(0, size - m), (0, size - m)])


@functools.partial(
    jax.jit,
    static_argnames=("epilogue", "coeffs", "block_m", "block_k", "interpret",
                     "out_dtype"))
def symmul_lower(
    a: jax.Array,
    b: jax.Array,
    *,
    epilogue: str = "plain",
    coeffs: Optional[tuple] = None,
    block_m: int = 128,
    block_k: int = 128,
    interpret: bool = False,
    out_dtype=None,
) -> jax.Array:
    """Raw lower-triangle product. a, b: (B, m, m). Returns (B, m, m) with the
    strict upper triangle UNWRITTEN — callers must ``ref.mirror_lower``.

    For ``epilogue='gram_poly'``, call with a == b == G and static (a,b,c) in
    ``coeffs``; the output is P = aI + bG + cG² (lower blocks).
    """
    if a.ndim != 3 or b.ndim != 3:
        raise ValueError(f"expected (B, m, m) operands, got {a.shape}, {b.shape}")
    if a.shape != b.shape or a.shape[-1] != a.shape[-2]:
        raise ValueError(f"symmul expects equal square operands, got {a.shape}, {b.shape}")
    if epilogue not in ("plain", "gram_poly"):
        raise ValueError(f"unknown epilogue {epilogue!r}")
    if epilogue == "gram_poly" and (coeffs is None or len(coeffs) != 3):
        raise ValueError("gram_poly epilogue requires static (a, b, c) coeffs")

    batch, m, _ = a.shape
    out_dtype = out_dtype or a.dtype
    # A block no wider than the operand padded to the 128-lane tiling: the
    # whole dim below 128, else a multiple of 128 the TPU lowering accepts
    # (clamping to an unaligned m would pad to lcm(m, bk) instead).
    cap = m if m < 128 else -(-m // 128) * 128
    bm = min(block_m, cap)
    bk = min(block_k, cap)
    # Pad both axes to a common multiple of the row- and k-block sizes so the
    # (i, j) block tables index every operand consistently.
    step = math.lcm(bm, bk)
    mp = ((m + step - 1) // step) * step
    a_p = _pad_square(a, mp)
    b_p = _pad_square(b, mp)
    nb, nk = mp // bm, mp // bk
    ii, jj = tri_index_tables(nb)
    n_lower = len(ii)

    in_specs = [
        pl.BlockSpec((1, bm, bk), lambda bi, l, k, ii, jj: (bi, ii[l], k)),
        pl.BlockSpec((1, bk, bm), lambda bi, l, k, ii, jj: (bi, k, jj[l])),
    ]
    operands = [a_p, b_p]
    if epilogue == "gram_poly":
        # G operand for the fused polynomial epilogue, pinned at (i, j).
        in_specs.append(pl.BlockSpec(
            (1, bm, bm), lambda bi, l, k, ii, jj: (bi, ii[l], jj[l])))
        operands.append(a_p)
        kernel = functools.partial(_gram_poly_kernel, nk=nk, bm=bm, coeffs=coeffs)
    else:
        kernel = functools.partial(_plain_kernel, nk=nk)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(batch, n_lower, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, bm, bm), lambda bi, l, k, ii, jj: (bi, ii[l], jj[l])),
        scratch_shapes=[pltpu.VMEM((bm, bm), jnp.float32)],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((batch, mp, mp), out_dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        name=f"symmul_{epilogue}",
    )(jnp.asarray(ii), jnp.asarray(jj), *operands)
    return out[:, :m, :m]
