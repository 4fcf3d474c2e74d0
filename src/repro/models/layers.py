"""Model-zoo building blocks, pure functional JAX (no flax).

Every layer is an (init, apply) pair; parameters are plain dicts whose key
paths drive both Muon dedication (core/dedication.py name rules) and the
TP sharding rules (models/sharding.py).  All matmuls run in the configured
compute dtype with fp32 accumulation; params are created in ``param_dtype``.

Conventions:
  * linear weights are stored (in_dim, out_dim) — activations @ W
  * stacked-layer leaves carry a leading L dim (built by vmap'd init),
    consumed by lax.scan in the backbones
  * attention caches are preallocated (B, S_max, kv, hd) with
    dynamic_update_slice writes at the decode position
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import flash

Params = Dict[str, Any]


# ---------------------------------------------------------------- utilities

def dot(x: jax.Array, w: jax.Array) -> jax.Array:
    """x @ w with fp32 accumulation, output in x.dtype."""
    return jax.lax.dot_general(
        x, w.astype(x.dtype),
        dimension_numbers=(((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(x.dtype)


def linear_init(key, d_in: int, d_out: int, *, bias: bool = False,
                dtype=jnp.float32, scale: Optional[float] = None) -> Params:
    scale = 1.0 / math.sqrt(d_in) if scale is None else scale
    p = {"w": (jax.random.normal(key, (d_in, d_out), jnp.float32)
               * scale).astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def linear(p: Params, x: jax.Array) -> jax.Array:
    y = dot(x, p["w"])
    if "b" in p:
        y = y + p["b"].astype(y.dtype)
    return y


def rmsnorm_init(d: int, dtype=jnp.float32) -> Params:
    return {"norm_scale": jnp.ones((d,), dtype)}


def rmsnorm(p: Params, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * p["norm_scale"].astype(jnp.float32)).astype(x.dtype)


def embed_init(key, vocab: int, d: int, dtype=jnp.float32) -> Params:
    return {"embedding": (jax.random.normal(key, (vocab, d), jnp.float32)
                          * 0.02).astype(dtype)}


def embed(p: Params, tokens: jax.Array) -> jax.Array:
    return jnp.take(p["embedding"], tokens, axis=0)


def unembed(p: Params, x: jax.Array) -> jax.Array:
    """Tied or untied output head: logits in fp32."""
    return jax.lax.dot_general(
        x, p["embedding"].astype(x.dtype),
        dimension_numbers=(((x.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


# ------------------------------------------------------------------- rotary

def rope_freqs(head_dim: int, max_pos: int, theta: float = 10000.0):
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                           / head_dim))
    t = jnp.arange(max_pos, dtype=jnp.float32)
    ang = jnp.outer(t, inv)                       # (S, hd/2)
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (..., S, H, hd); cos/sin: (S, hd/2) already position-gathered."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    cos = cos[..., :, None, :]
    sin = sin[..., :, None, :]
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x1 * sin + x2 * cos], axis=-1).astype(x.dtype)


# ---------------------------------------------------------------- attention

@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    sliding_window: Optional[int] = None   # None = full causal
    causal: bool = True                    # False for encoder / cross attn
    # sequence-sharded attention: when the head counts do not divide the
    # 'model' axis, GSPMD replicates the whole attention computation over it;
    # pinning q/output to (batch_axes, seq_axis) shards the score/AV einsums
    # over the sequence instead (k/v gathered once per layer).
    batch_axes: Optional[tuple] = None
    seq_axis: Optional[str] = None


def attention_init(key, cfg: AttnConfig, dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 4)
    H, KV, hd, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_model
    return {
        "q_proj": linear_init(ks[0], d, H * hd, bias=cfg.qkv_bias, dtype=dtype),
        "k_proj": linear_init(ks[1], d, KV * hd, bias=cfg.qkv_bias, dtype=dtype),
        "v_proj": linear_init(ks[2], d, KV * hd, bias=cfg.qkv_bias, dtype=dtype),
        "o_proj": linear_init(ks[3], H * hd, d, dtype=dtype),
    }


_Q_CHUNK = 1024
_KV_CHUNK = 1024


def _pick_chunk(n: int, target: int) -> int:
    """Largest divisor of n that is <= target."""
    best = 1
    for c in range(1, min(n, target) + 1):
        if n % c == 0:
            best = c
    return best


def _block_mask(qpos, kpos, *, causal, window, window_enabled):
    """Boolean mask from absolute positions, built on the fly.  qpos is
    (qlen,) — or (B, qlen) when decode rows sit at per-slot positions
    (continuous batching) — giving a (qlen, klen) / (B, qlen, klen) mask."""
    if not causal:
        return None
    ok = qpos[..., :, None] >= kpos
    if window is not None:
        okw = ok & (kpos > qpos[..., :, None] - window)
        if window_enabled is None:
            ok = okw
        else:  # traced per-layer flag (uniform-scan hybrid blocks)
            ok = jnp.where(window_enabled, okw, ok)
    return ok


def _paged_write(leaf, new, block_table, pos, block_size):
    """Scatter new K/V rows into a paged pool leaf.

    leaf: (P, bs, ...) physical block pool; new: (B, S, ...) freshly
    projected rows.  Vector ``pos`` (decode, S == 1): row b writes at
    physical block ``table[b, pos[b] // bs]`` offset ``pos[b] % bs``.
    Scalar ``pos`` (chunked prefill, B == 1): the S chunk rows write at
    logical positions pos + arange(S) through row 0's table.  Live block
    tables are injective (paged.BlockPool), so scatter indices never
    collide across slots; free slots idle on the reserved null block 0,
    which no live table ever maps."""
    if jnp.ndim(pos) == 0:
        p = pos + jnp.arange(new.shape[1])
        pb = block_table[0, p // block_size]
        return leaf.at[pb, p % block_size].set(new[0].astype(leaf.dtype))
    pb = jnp.take_along_axis(block_table, (pos // block_size)[:, None],
                             axis=1)[:, 0]
    return leaf.at[pb, pos % block_size].set(new[:, 0].astype(leaf.dtype))


def _paged_read(leaf, block_table):
    """Gather a slot-contiguous (B, W*bs, ...) sequence view from the
    (P, bs, ...) pool: logical block j of row b is ``leaf[table[b, j]]``.
    Entries past a slot's allocated length point at the null block; the
    causal mask (kpos <= qpos) guarantees they are never attended."""
    B, W = block_table.shape
    g = leaf[block_table]                       # (B, W, bs, ...)
    return g.reshape((B, W * leaf.shape[1]) + leaf.shape[2:])


def _one_device(x: jax.Array) -> bool:
    """Whether x's program runs on one device: no mesh of more than one
    device on its type (committed mesh-sharded inputs put theirs there) nor
    set around the trace.  A Pallas call on a larger mesh would need a
    ``shard_map``."""
    return (jax.typeof(x).sharding.mesh.size <= 1
            and jax.sharding.get_abstract_mesh().size <= 1)


def _sdpa(q, k, v, *, scale, qpos=None, kpos=None, causal=False,
          window=None, window_enabled=None, q_one_block=False):
    """q: (B,S,H,hd); k,v: (B,T,KV,·); GQA by head-group repetition.

    Long sequences take the chunked online-softmax path (flash-attention
    pattern: O(S·chunk) memory instead of O(S·T) materialized probabilities —
    the TPU-native memory discipline the 32k/500k shapes require).  Masks are
    never materialized at (S, T): they are rebuilt per block from positions.
    """
    B, S, H, hd = q.shape
    T = k.shape[1]
    KV = k.shape[2]
    rep = H // KV
    hv = v.shape[-1]
    qg = q.reshape(B, S, KV, rep, hd)
    if qpos is None:
        qpos = jnp.arange(S)
    if kpos is None:
        kpos = jnp.arange(T)

    if S > _Q_CHUNK and T > _KV_CHUNK:
        out = _chunked_sdpa(qg, k, v, scale, qpos, kpos, causal, window,
                            window_enabled, q_one_block=q_one_block)
        return out.reshape(B, S, H, hv).astype(q.dtype)

    logits = jnp.einsum("bsgrh,btgh->bgrst", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    mask = _block_mask(qpos, kpos, causal=causal, window=window,
                       window_enabled=window_enabled)
    if mask is not None:
        # (S,T) shared positions, or (B,S,T) per-row decode positions
        mask = mask[None, None, None] if mask.ndim == 2 \
            else mask[:, None, None]
        logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bgrst,btgh->bsgrh", probs, v.astype(jnp.float32))
    # v's head dim may differ from q/k's (MLA: v_head_dim != qk dims)
    return out.reshape(B, S, H, hv).astype(q.dtype)


def _chunked_sdpa(qg, k, v, scale, qpos, kpos, causal, window,
                  window_enabled, q_one_block=False):
    """Online-softmax attention: lax.map over query blocks × lax.scan over
    KV blocks, fp32 running (max, denom, acc).

    ``q_one_block``: keep the whole query axis as a single block (scan only
    over KV).  Used when q is sequence-sharded over 'model' — lax.map over a
    sharded block axis would be a *sequential* scan over a sharded dim,
    which silently replicates (docs/DESIGN.md §9, qwen prefill)."""
    B, S, G, R, hd = qg.shape
    T = k.shape[1]
    hv = v.shape[-1]
    qc = S if q_one_block else _pick_chunk(S, _Q_CHUNK)
    kc = _pick_chunk(T, _KV_CHUNK)
    nq, nk = S // qc, T // kc

    qb = jnp.moveaxis(qg.reshape(B, nq, qc, G, R, hd), 1, 0)
    qpb = qpos.reshape(nq, qc)
    kb = jnp.moveaxis(k.reshape(B, nk, kc, G, hd), 1, 0)
    vb = jnp.moveaxis(v.reshape(B, nk, kc, G, hv), 1, 0)
    kpb = kpos.reshape(nk, kc)

    def q_block(args):
        q_i, qpos_i = args

        def kv_step(carry, xs):
            m, l, acc = carry
            k_j, v_j, kpos_j = xs
            logits = jnp.einsum("bqgrh,bkgh->bqgrk",
                                q_i.astype(jnp.float32),
                                k_j.astype(jnp.float32)) * scale
            ok = _block_mask(qpos_i, kpos_j, causal=causal, window=window,
                             window_enabled=window_enabled)
            if ok is not None:
                logits = jnp.where(ok[None, :, None, None, :], logits, -1e30)
            m_new = jnp.maximum(m, logits.max(-1))
            p = jnp.exp(logits - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + p.sum(-1)
            # probabilities cross to the AV product in the value dtype
            # (bf16 on TPU) with fp32 accumulation — halves the dominant
            # probs traffic of the prefill cells; a no-op under fp32 compute
            acc = acc * corr[..., None] + jnp.einsum(
                "bqgrk,bkgh->bqgrh", p.astype(v_j.dtype), v_j,
                preferred_element_type=jnp.float32)
            return (m_new, l, acc), None

        init = (jnp.full((B, qc, G, R), -1e30, jnp.float32),
                jnp.zeros((B, qc, G, R), jnp.float32),
                jnp.zeros((B, qc, G, R, hv), jnp.float32))
        (m, l, acc), _ = jax.lax.scan(kv_step, init, (kb, vb, kpb))
        return acc / jnp.maximum(l, 1e-30)[..., None]

    out = jax.lax.map(q_block, (qb, qpb))          # (nq, B, qc, G, R, hv)
    return jnp.moveaxis(out, 0, 1).reshape(B, S, G, R, hv)


def attention(p: Params, cfg: AttnConfig, x: jax.Array, *,
              xk: Optional[jax.Array] = None,
              cache: Optional[Tuple[jax.Array, jax.Array]] = None,
              pos: Optional[jax.Array] = None,
              rope_cs: Optional[Tuple[jax.Array, jax.Array]] = None,
              window_enabled: Optional[jax.Array] = None,
              static_cache: bool = False,
              block_table: Optional[jax.Array] = None):
    """Self (xk=None) or cross attention with optional KV cache.

    cache: (k_cache, v_cache) of (B, S_max, KV, hd); pos: write position —
    a scalar shared by every row (prefill / lockstep decode) or a (B,)
    vector of per-row positions (slot-based continuous batching, S == 1).
    window_enabled: traced bool selecting the sliding window mask at runtime
    (uniform-scan hybrid layers).  static_cache: use the cache as-is without
    recomputing/updating K,V (decode-time cross attention over precomputed
    encoder KV).
    block_table: (B, W) int32 map of logical cache blocks to physical pool
    blocks — the cache leaves are then (P, bs, KV, hd) pools shared by every
    row, written through ``_paged_write`` and read back as a gathered
    (B, W·bs, KV, hd) view (paged KV, docs/DESIGN.md §12).
    Returns (out, new_cache).
    """
    B, S, _ = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = linear(p["q_proj"], x).reshape(B, S, H, hd)

    if static_cache:
        assert cache is not None
        k, v = cache
        out = _sdpa(q, k, v, scale=1.0 / math.sqrt(hd))
        return linear(p["o_proj"], out.reshape(B, S, H * hd)), cache

    src = x if xk is None else xk
    k = linear(p["k_proj"], src).reshape(B, src.shape[1], KV, hd)
    v = linear(p["v_proj"], src).reshape(B, src.shape[1], KV, hd)

    if rope_cs is not None and xk is None:
        cos_q, sin_q, cos_k, sin_k = rope_cs
        q = apply_rope(q, cos_q, sin_q)
        k = apply_rope(k, cos_k, sin_k)

    new_cache = None
    if cache is not None and block_table is not None:
        assert xk is None, "paged cache is a self-attention path"
        kc, vc = cache                       # (P, bs, KV, hd) pools
        bs = kc.shape[1]
        kc = _paged_write(kc, k, block_table, pos, bs)
        vc = _paged_write(vc, v, block_table, pos, bs)
        new_cache = (kc, vc)
        k = _paged_read(kc, block_table)
        v = _paged_read(vc, block_table)
    elif cache is not None:
        kc, vc = cache
        if xk is None:  # self-attn decode/prefill cache update
            if jnp.ndim(pos) == 0:
                kc = jax.lax.dynamic_update_slice(kc, k.astype(kc.dtype),
                                                  (0, pos, 0, 0))
                vc = jax.lax.dynamic_update_slice(vc, v.astype(vc.dtype),
                                                  (0, pos, 0, 0))
            else:  # per-row slot positions: scatter one row each
                assert S == 1, "vector pos is a single-token decode path"
                rows = jnp.arange(B)
                kc = kc.at[rows, pos].set(k[:, 0].astype(kc.dtype))
                vc = vc.at[rows, pos].set(v[:, 0].astype(vc.dtype))
        k, v = kc, vc
        new_cache = (kc, vc)

    T = k.shape[1]
    if cfg.seq_axis is not None and S > 1:
        from jax.sharding import PartitionSpec as _P
        pin = _P(cfg.batch_axes, cfg.seq_axis, None, None)
        q = jax.lax.with_sharding_constraint(q, pin)
        # k/v replicated over the seq axis (each q block reads all of them);
        # otherwise GSPMD shards the contracting head_dim and emits an
        # all-reduce per attention block (docs/DESIGN.md §9, qwen prefill)
        kv_pin = _P(cfg.batch_axes, None, None, None)
        k = jax.lax.with_sharding_constraint(k, kv_pin)
        v = jax.lax.with_sharding_constraint(v, kv_pin)
    seq_pinned = cfg.seq_axis is not None and S > 1
    scale = 1.0 / math.sqrt(hd)
    if not cfg.causal or xk is not None:
        out = _sdpa(q, k, v, scale=scale, q_one_block=seq_pinned)
    else:
        offset = pos if pos is not None else 0
        qpos = offset[:, None] + jnp.arange(S) if jnp.ndim(offset) == 1 \
            else offset + jnp.arange(S)

        def sdpa(q, k, v):
            return _sdpa(q, k, v, scale=scale,
                         qpos=qpos, kpos=jnp.arange(T), causal=True,
                         window=cfg.sliding_window,
                         window_enabled=window_enabled,
                         q_one_block=seq_pinned)

        if (cache is None and pos is None and cfg.sliding_window is None
                and not seq_pinned and S == T and S > _Q_CHUNK
                and S % flash.BLOCK == 0 and _one_device(q)):
            # long causal self-attention in training: the flash-attention
            # kernel wherever this is lowered for a TPU (a compile for a
            # described one too); other platforms keep the chunked softmax
            out = jax.lax.platform_dependent(
                q, k, v, default=sdpa,
                tpu=functools.partial(flash.causal_attention, scale=scale,
                                      interpret=False))
        else:
            out = sdpa(q, k, v)
    if cfg.seq_axis is not None and S > 1:
        out = jax.lax.with_sharding_constraint(
            out, _P(cfg.batch_axes, cfg.seq_axis, None, None))
    out = linear(p["o_proj"], out.reshape(B, S, H * hd))
    return out, new_cache


# -------------------------------------------------------- MLA (DeepSeek-V3)

@dataclasses.dataclass(frozen=True)
class MLAConfig:
    d_model: int
    n_heads: int
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 10000.0


def mla_init(key, cfg: MLAConfig, dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 7)
    H = cfg.n_heads
    qh = cfg.qk_nope_dim + cfg.qk_rope_dim
    return {
        "q_a_proj": linear_init(ks[0], cfg.d_model, cfg.q_lora_rank, dtype=dtype),
        "q_a_norm": rmsnorm_init(cfg.q_lora_rank, dtype),
        "q_b_proj": linear_init(ks[1], cfg.q_lora_rank, H * qh, dtype=dtype),
        "kv_a_proj": linear_init(ks[2], cfg.d_model,
                                 cfg.kv_lora_rank + cfg.qk_rope_dim, dtype=dtype),
        "kv_a_norm": rmsnorm_init(cfg.kv_lora_rank, dtype),
        "kv_b_proj": linear_init(ks[3], cfg.kv_lora_rank,
                                 H * (cfg.qk_nope_dim + cfg.v_head_dim),
                                 dtype=dtype),
        "o_proj": linear_init(ks[4], H * cfg.v_head_dim, cfg.d_model,
                              dtype=dtype),
    }


def mla_attention(p: Params, cfg: MLAConfig, x: jax.Array, *,
                  cache: Optional[Tuple[jax.Array, jax.Array]] = None,
                  pos: Optional[jax.Array] = None,
                  rope_cs=None,
                  block_table: Optional[jax.Array] = None):
    """Multi-head Latent Attention.  Cache holds (c_kv, k_rope): the latent
    (B, S_max, kv_lora) plus shared rope key (B, S_max, 1, rope_dim) — the
    memory saving that defines MLA.  With ``block_table`` both leaves are
    (P, bs, ...) pools indirected per row, same contract as attention()."""
    B, S, _ = x.shape
    H = cfg.n_heads
    nd, rd, vd = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim

    q = linear(p["q_b_proj"], rmsnorm(p["q_a_norm"], linear(p["q_a_proj"], x)))
    q = q.reshape(B, S, H, nd + rd)
    q_nope, q_rope = q[..., :nd], q[..., nd:]

    kv_a = linear(p["kv_a_proj"], x)
    c_kv, k_rope = kv_a[..., :cfg.kv_lora_rank], kv_a[..., cfg.kv_lora_rank:]
    c_kv = rmsnorm(p["kv_a_norm"], c_kv)
    k_rope = k_rope.reshape(B, S, 1, rd)

    if rope_cs is not None:
        cos_q, sin_q, cos_k, sin_k = rope_cs
        q_rope = apply_rope(q_rope, cos_q, sin_q)
        k_rope = apply_rope(k_rope, cos_k, sin_k)

    new_cache = None
    if cache is not None and block_table is not None:
        cc, rc = cache                       # (P, bs, ...) pools
        bs = cc.shape[1]
        cc = _paged_write(cc, c_kv, block_table, pos, bs)
        rc = _paged_write(rc, k_rope, block_table, pos, bs)
        new_cache = (cc, rc)
        c_kv = _paged_read(cc, block_table)
        k_rope = _paged_read(rc, block_table)
    elif cache is not None:
        cc, rc = cache
        if jnp.ndim(pos) == 0:
            cc = jax.lax.dynamic_update_slice(cc, c_kv.astype(cc.dtype),
                                              (0, pos, 0))
            rc = jax.lax.dynamic_update_slice(rc, k_rope.astype(rc.dtype),
                                              (0, pos, 0, 0))
        else:  # per-row slot positions (continuous batching)
            assert S == 1, "vector pos is a single-token decode path"
            rows = jnp.arange(B)
            cc = cc.at[rows, pos].set(c_kv[:, 0].astype(cc.dtype))
            rc = rc.at[rows, pos].set(k_rope[:, 0].astype(rc.dtype))
        c_kv, k_rope = cc, rc
        new_cache = (cc, rc)

    kv = linear(p["kv_b_proj"], c_kv).reshape(B, c_kv.shape[1], H, nd + vd)
    k_nope, v = kv[..., :nd], kv[..., nd:]
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, k_nope.shape[:-1] + (rd,))], -1)
    qf = jnp.concatenate([q_nope, q_rope], -1)

    T = k.shape[1]
    offset = pos if pos is not None else 0
    qpos = offset[:, None] + jnp.arange(S) if jnp.ndim(offset) == 1 \
        else offset + jnp.arange(S)
    out = _sdpa(qf, k, v, scale=1.0 / math.sqrt(nd + rd),
                qpos=qpos, kpos=jnp.arange(T), causal=True)
    return linear(p["o_proj"], out.reshape(B, S, H * vd)), new_cache


# --------------------------------------------------------------------- MLPs

def mlp_init(key, d: int, d_ff: int, act: str, dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 3)
    p = {"up_proj": linear_init(ks[0], d, d_ff, dtype=dtype),
         "down_proj": linear_init(ks[1], d_ff, d, dtype=dtype)}
    if act == "swiglu":
        p["gate_proj"] = linear_init(ks[2], d, d_ff, dtype=dtype)
    return p


def mlp(p: Params, x: jax.Array, act: str) -> jax.Array:
    up = linear(p["up_proj"], x)
    if act == "swiglu":
        h = jax.nn.silu(linear(p["gate_proj"], x)) * up
    elif act == "squared_relu":      # nemotron-4
        h = jnp.square(jax.nn.relu(up))
    elif act == "gelu":
        h = jax.nn.gelu(up)
    else:
        raise ValueError(f"unknown act {act!r}")
    return linear(p["down_proj"], h)
