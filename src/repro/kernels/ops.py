"""Public jit'd wrappers over the Pallas kernels.

These are the ops the Gram NS iteration (core/gram_ns.py) dispatches to when
``use_kernels=True``.  Each op:

  * accepts arbitrary leading batch dims (flattened internally to one),
  * runs the lower-triangle Pallas kernel (symmul.py / gram_syrk.py),
  * mirrors the strict lower triangle up to reconstruct the dense symmetric
    output the next step consumes (ref.mirror_lower),
  * consults the autotuner cache for block shapes unless explicit
    ``block_m/block_k`` are given.

Interpret mode is chosen from the platform (``repro.kernels.interpret``):
interpreted on the CPU backend, compiled on the TPU.
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.kernels import interpret, ref
from repro.kernels.gram_syrk import syrk_lower
from repro.kernels.symmul import symmul_lower


def _flatten_batch(x):
    lead = x.shape[:-2]
    return x.reshape((-1,) + x.shape[-2:]), lead


def _resolve_blocks(m: int, k: int, block_m: Optional[int],
                    block_k: Optional[int], mode: str, dtype) -> tuple[int, int]:
    if block_m is not None and block_k is not None:
        return block_m, block_k
    from repro.kernels.autotune import lookup  # lazy: avoid import cycle
    bm, bk = lookup(mode, m, k, str(jnp.dtype(dtype)))
    return (block_m or bm, block_k or bk)


def syrk(x, *, block_m: Optional[int] = None, block_k: Optional[int] = None,
         out_dtype=None):
    """G = X Xᵀ (dense symmetric output) for x of shape (..., m, n)."""
    xf, lead = _flatten_batch(x)
    bm, bk = _resolve_blocks(xf.shape[-2], xf.shape[-1], block_m, block_k,
                             "syrk", xf.dtype)
    raw = syrk_lower(xf, block_m=bm, block_k=bk, interpret=interpret(),
                     out_dtype=out_dtype)
    return ref.mirror_lower(raw).reshape(lead + raw.shape[-2:])


def symmul(a, b, *, block_m: Optional[int] = None,
           block_k: Optional[int] = None, out_dtype=None):
    """C = A B for symmetric commuting A, B of shape (..., m, m)."""
    af, lead = _flatten_batch(a)
    bf, _ = _flatten_batch(b)
    bm, bk = _resolve_blocks(af.shape[-1], af.shape[-1], block_m, block_k,
                             "symmul", af.dtype)
    raw = symmul_lower(af, bf, epilogue="plain", block_m=bm, block_k=bk,
                       interpret=interpret(), out_dtype=out_dtype)
    return ref.mirror_lower(raw).reshape(lead + raw.shape[-2:])


def gram_poly(g, a: float, b: float, c: float, *,
              block_m: Optional[int] = None, block_k: Optional[int] = None,
              out_dtype=None):
    """P = aI + bG + cG² with the polynomial fused into the G@G epilogue."""
    gf, lead = _flatten_batch(g)
    bm, bk = _resolve_blocks(gf.shape[-1], gf.shape[-1], block_m, block_k,
                             "gram_poly", gf.dtype)
    raw = symmul_lower(gf, gf, epilogue="gram_poly",
                       coeffs=(float(a), float(b), float(c)),
                       block_m=bm, block_k=bk, interpret=interpret(),
                       out_dtype=out_dtype)
    return ref.mirror_lower(raw).reshape(lead + raw.shape[-2:])
