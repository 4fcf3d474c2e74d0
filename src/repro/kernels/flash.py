"""Causal flash attention for training: Pallas TPU kernels with their own VJP.

The forward computes softmax(q kᵀ · scale) v block by block with an online
softmax and saves only (q, k, v, out, logsumexp).  The backward has a dq
kernel and a dk/dv kernel; both recompute each block's probabilities in
VMEM from the saved logsumexp.  All three skip the blocks above the causal
diagonal: their index maps repeat the last block a row of the grid needs,
so the skipped steps neither compute nor copy.  Scores never reach HBM.

Grouped-query attention is native: query head h reads KV head h // R (R
query heads per KV head), and the dk/dv kernel sums the R heads of a group
in VMEM.

Precision: q, k and v enter in float32 and out, dq, dk and dv leave in
float32; every product takes float32 tiles at Mosaic's default contraction,
which rounds them to bfloat16 and accumulates in float32 (checked on a TPU
v5e against ``jnp.dot``; PERF.md), the precision XLA gives the same
products by default.  The softmax and its statistics are float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret as _interpret

# Query and key block: the fastest forward + backward of a sweep on a TPU
# v5e at S 2048, head_dim 64 and 128 (PERF.md).  Query block i attends to
# key blocks 0..i; the diagonal block is masked.
BLOCK = 1024

_MASK = -0.7 * float(np.finfo(np.float32).max)
_LANES = 128                        # row statistics, replicated over lanes
_NT = (((1,), (1,)), ((), ()))      # a @ bᵀ


def _logits(q, k, scale, i, j):
    """Scaled scores of query block i against key block j, masked above
    the causal diagonal."""
    s = jax.lax.dot_general(q, k, _NT,
                            preferred_element_type=jnp.float32) * scale
    rows = i * BLOCK + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    cols = j * BLOCK + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(cols <= rows, s, _MASK)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc, l_sc, acc_sc, *,
                scale):
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _():
        m_sc[...] = jnp.full_like(m_sc, _MASK)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    @pl.when(j <= i)
    def _():
        s = _logits(q_ref[0, 0], k_ref[0, 0], scale, i, j)
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new[:, :1])
        alpha = jnp.exp(m_prev - m_new)
        l_sc[...] = alpha * l_sc[...] + p.sum(axis=1, keepdims=True)
        m_sc[...] = m_new
        acc_sc[...] = acc_sc[...] * alpha[:, :1] + jnp.dot(
            p, v_ref[0, 0], preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(3) - 1)
    def _():
        l = l_sc[...]
        o_ref[0, 0] = (acc_sc[...] / l[:, :1]).astype(o_ref.dtype)
        lse_ref[0, 0] = m_sc[...] + jnp.log(l)


def _probs_and_dscores(q, k, v, do, lse, di, scale, i, j):
    """Recomputed probabilities p and score gradients ds of one block."""
    p = jnp.exp(_logits(q, k, scale, i, j) - lse[:, :1])
    dp = jax.lax.dot_general(do, v, _NT, preferred_element_type=jnp.float32)
    return p, p * (dp - di[:, :1])


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref, dq_sc,
               *, scale):
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _():
        dq_sc[...] = jnp.zeros_like(dq_sc)

    @pl.when(j <= i)
    def _():
        k = k_ref[0, 0]
        _, ds = _probs_and_dscores(q_ref[0, 0], k, v_ref[0, 0], do_ref[0, 0],
                                   lse_ref[0, 0], di_ref[0, 0], scale, i, j)
        dq_sc[...] += jnp.dot(ds, k, preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(3) - 1)
    def _():
        dq_ref[0, 0] = (dq_sc[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref, dv_ref,
                dk_sc, dv_sc, *, scale):
    j, r, i = pl.program_id(2), pl.program_id(3), pl.program_id(4)

    @pl.when((r == 0) & (i == 0))
    def _():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    @pl.when(i >= j)
    def _():
        q, do = q_ref[0, 0], do_ref[0, 0]
        p, ds = _probs_and_dscores(q, k_ref[0, 0], v_ref[0, 0], do,
                                   lse_ref[0, 0], di_ref[0, 0], scale, i, j)
        dv_sc[...] += jnp.dot(p.T, do, preferred_element_type=jnp.float32)
        dk_sc[...] += jnp.dot(ds.T, q, preferred_element_type=jnp.float32)

    @pl.when((r == pl.num_programs(3) - 1) & (i == pl.num_programs(4) - 1))
    def _():
        dk_ref[0, 0] = (dk_sc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_sc[...].astype(dv_ref.dtype)


def _params(n_parallel: int, n_arbitrary: int):
    # 1024 x 1024 blocks of float32 scores need more than the default 16 MiB
    # of scoped VMEM once XLA keeps an operand there (a v5e has 128 MiB)
    return pltpu.CompilerParams(
        dimension_semantics=(("parallel",) * n_parallel
                             + ("arbitrary",) * n_arbitrary),
        vmem_limit_bytes=48 * 2**20)


def _forward(q, k, v, scale, interpret):
    B, H, S, hd = q.shape
    R = H // k.shape[1]
    n = S // BLOCK

    def row_map(b, h, i, j):
        return b, h, i, 0

    def key_map(b, h, i, j):    # above the diagonal: keep block i, no copy
        return b, h // R, jnp.minimum(j, i), 0

    rows = pl.BlockSpec((1, 1, BLOCK, hd), row_map)
    keys = pl.BlockSpec((1, 1, BLOCK, hd), key_map)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale),
        grid=(B, H, n, n),
        in_specs=[rows, keys, keys],
        out_specs=[rows, pl.BlockSpec((1, 1, BLOCK, _LANES), row_map)],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((B, H, S, _LANES), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((BLOCK, _LANES), jnp.float32),
                        pltpu.VMEM((BLOCK, _LANES), jnp.float32),
                        pltpu.VMEM((BLOCK, hd), jnp.float32)],
        compiler_params=_params(3, 1),
        interpret=interpret,
    )(q, k, v)


def _backward(q, k, v, o, lse, do, scale, interpret):
    B, H, S, hd = q.shape
    KV = k.shape[1]
    R = H // KV
    n = S // BLOCK
    di = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    di = jnp.broadcast_to(di[..., None], lse.shape)

    # dq: grid over query blocks, key blocks innermost
    def row_map(b, h, i, j):
        return b, h, i, 0

    def key_map(b, h, i, j):
        return b, h // R, jnp.minimum(j, i), 0

    rows = pl.BlockSpec((1, 1, BLOCK, hd), row_map)
    stats = pl.BlockSpec((1, 1, BLOCK, _LANES), row_map)
    keys = pl.BlockSpec((1, 1, BLOCK, hd), key_map)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale),
        grid=(B, H, n, n),
        in_specs=[rows, keys, keys, rows, stats, stats],
        out_specs=rows,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((BLOCK, hd), jnp.float32)],
        compiler_params=_params(3, 1),
        interpret=interpret,
    )(q, k, v, do, lse, di)

    # dk, dv: grid over key blocks; the group's query heads and blocks inside
    def group_row_map(b, g, j, r, i):   # above the diagonal: keep block j
        return b, g * R + r, jnp.maximum(i, j), 0

    def group_key_map(b, g, j, r, i):
        return b, g, j, 0

    rows = pl.BlockSpec((1, 1, BLOCK, hd), group_row_map)
    stats = pl.BlockSpec((1, 1, BLOCK, _LANES), group_row_map)
    keys = pl.BlockSpec((1, 1, BLOCK, hd), group_key_map)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale),
        grid=(B, KV, n, R, n),
        in_specs=[rows, keys, keys, rows, stats, stats],
        out_specs=[keys, keys],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((BLOCK, hd), jnp.float32),
                        pltpu.VMEM((BLOCK, hd), jnp.float32)],
        compiler_params=_params(3, 2),
        interpret=interpret,
    )(q, k, v, do, lse, di)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _attend(q, k, v, scale, interpret):
    return _forward(q, k, v, scale, interpret)[0]


def _attend_fwd(q, k, v, scale, interpret):
    o, lse = _forward(q, k, v, scale, interpret)
    return o, (q, k, v, o, lse)


def _attend_bwd(scale, interpret, res, do):
    q, k, v, o, lse = res
    return _backward(q, k, v, o, lse, do, scale, interpret)


_attend.defvjp(_attend_fwd, _attend_bwd)


def causal_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                     scale: float, interpret: bool | None = None
                     ) -> jax.Array:
    """Causal self-attention over positions ``arange(S)`` for q and k.

    q: (B, S, H, hd); k, v: (B, S, KV, hd) with H a multiple of KV and S a
    multiple of ``BLOCK``.  Returns (B, S, H, hd) in q's dtype.
    ``interpret`` defaults to ``repro.kernels.interpret()``; a caller that
    lowers this only for a TPU passes False, so that a compile for a
    described TPU on a CPU host holds the Mosaic kernels.
    """
    S = q.shape[1]
    assert S % BLOCK == 0, (S, BLOCK)
    if interpret is None:
        interpret = _interpret()
    heads_major = functools.partial(jnp.swapaxes, axis1=1, axis2=2)
    out = _attend(heads_major(q), heads_major(k), heads_major(v),
                  float(scale), bool(interpret))
    return heads_major(out)
