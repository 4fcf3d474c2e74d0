"""Decoder-LM backbone covering the dense / moe / ssm / hybrid / xlstm / vlm
families, with train, prefill and decode entry points.

One scan-over-layers implementation (stacked params, remat-able body) serves
every family; the block mixer is selected by ``ArchConfig.family``:

  dense   — GQA/MHA attention + MLP (swiglu or squared-relu, optional biases)
  moe     — attention + (MLA for deepseek) + MoE FFN with shared experts
  hybrid  — hymba: parallel attention ‖ mamba heads in every block, sliding-
            window attention except on ``global_layers``
  ssm     — xlstm: mLSTM blocks with sLSTM interleave (own layer loop)
  vlm     — dense backbone consuming [patch embeds ; token embeds]

Caches returned by ``prefill`` and consumed by ``decode_step`` are stacked
(L, ...) pytrees so decode also scans.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers, moe as moe_lib, ssm as ssm_lib, xlstm as xlstm_lib
from repro.models.layers import AttnConfig, MLAConfig

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                     # dense | moe | hybrid | ssm | audio | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None
    qkv_bias: bool = False
    act: str = "swiglu"
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    # attention variants
    attn_kind: str = "gqa"          # gqa | mla
    mla: Optional[MLAConfig] = None
    sliding_window: Optional[int] = None
    global_layers: Tuple[int, ...] = ()   # hymba: full-attn layer indices
    # moe
    moe: Optional[moe_lib.MoEConfig] = None
    # ssm / hybrid
    ssm: Optional[ssm_lib.SSMConfig] = None
    # xlstm
    xlstm: Optional[xlstm_lib.XLSTMConfig] = None
    # multimodal stub frontend
    frontend: Optional[str] = None  # 'patch' | 'frame'
    frontend_dim: int = 1024
    frontend_len: int = 576
    # encoder-decoder
    encdec: bool = False
    n_enc_layers: int = 0
    # dtype policy
    param_dtype: str = "float32"
    compute_dtype: str = "float32"
    remat: bool = True
    # activation batch sharding pinned at every block boundary (FSDP/ZeRO-3
    # discipline; requires lowering under a mesh context)
    act_batch_axes: Optional[Tuple[str, ...]] = None
    # sequence-sharded attention axis (archs whose head counts do not divide
    # the 'model' axis — see layers.AttnConfig.seq_axis)
    act_seq_axis: Optional[str] = None

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    def attn_cfg(self) -> AttnConfig:
        return AttnConfig(self.d_model, self.n_heads, self.n_kv_heads,
                          self.hd, qkv_bias=self.qkv_bias,
                          rope_theta=self.rope_theta,
                          sliding_window=self.sliding_window,
                          batch_axes=self.act_batch_axes,
                          seq_axis=self.act_seq_axis)

    def sub_quadratic(self) -> bool:
        """Eligible for the long_500k shape (assignment rule)."""
        return self.family in ("ssm",) or (
            self.family == "hybrid" and self.sliding_window is not None)

    def param_count(self) -> int:
        """Total parameters (for 6·N·D roofline bookkeeping)."""
        import numpy as np
        shapes = jax.eval_shape(partial(init, self), jax.random.PRNGKey(0))
        return int(sum(np.prod(l.shape) for l in jax.tree.leaves(shapes)))

    def active_param_count(self) -> int:
        """Active params per token (MoE: shared + top_k experts only)."""
        n = self.param_count()
        if self.moe is None:
            return n
        c = self.moe
        per_expert = 3 * c.d_model * c.d_expert
        inactive = (c.n_experts - c.top_k) * per_expert * self.n_layers
        return n - inactive


# ----------------------------------------------------------- block (init)

def _block_init(cfg: ArchConfig, key) -> Params:
    ks = jax.random.split(key, 8)
    dt = jnp.dtype(cfg.param_dtype)
    p: Params = {"attn_norm": layers.rmsnorm_init(cfg.d_model, dt),
                 "mlp_norm": layers.rmsnorm_init(cfg.d_model, dt)}
    if cfg.family == "ssm":
        raise AssertionError("xlstm family uses its own init path")
    if cfg.attn_kind == "mla":
        p["attn"] = layers.mla_init(ks[0], cfg.mla, dt)
    else:
        p["attn"] = layers.attention_init(ks[0], cfg.attn_cfg(), dt)
    if cfg.family == "hybrid":
        p["ssm"] = ssm_lib.ssm_init(ks[1], cfg.ssm, dt)
        p["mix_scale"] = jnp.ones((2,), dt)   # learned attn/ssm balance
    if cfg.moe is not None:
        p["moe"] = moe_lib.moe_init(ks[2], cfg.moe, dt)
    else:
        p["mlp"] = layers.mlp_init(ks[3], cfg.d_model, cfg.d_ff, cfg.act, dt)
    return p


def init(cfg: ArchConfig, key) -> Params:
    ks = jax.random.split(key, 6)
    dt = jnp.dtype(cfg.param_dtype)
    p: Params = {"embed": layers.embed_init(ks[0], cfg.vocab, cfg.d_model, dt),
                 "final_norm": layers.rmsnorm_init(cfg.d_model, dt)}
    if not cfg.tie_embeddings:
        p["lm_head"] = {"embedding": (jax.random.normal(
            ks[1], (cfg.vocab, cfg.d_model), jnp.float32) * 0.02).astype(dt)}
    if cfg.family == "ssm":       # xlstm
        xc = cfg.xlstm
        n_s = cfg.n_layers // xc.slstm_every
        n_m = cfg.n_layers - n_s
        p["mlstm_blocks"] = jax.vmap(
            lambda k: _xlstm_block_init(cfg, k, "mlstm"))(
                jax.random.split(ks[2], n_m))
        if n_s:
            p["slstm_blocks"] = jax.vmap(
                lambda k: _xlstm_block_init(cfg, k, "slstm"))(
                    jax.random.split(ks[3], n_s))
    else:
        p["blocks"] = jax.vmap(lambda k: _block_init(cfg, k))(
            jax.random.split(ks[2], cfg.n_layers))
    if cfg.frontend is not None:
        p["frontend_proj"] = {
            "fc1": layers.linear_init(ks[4], cfg.frontend_dim,
                                      cfg.d_model, dtype=dt),
            "fc2": layers.linear_init(ks[5], cfg.d_model, cfg.d_model,
                                      dtype=dt)}
    return p


def _xlstm_block_init(cfg: ArchConfig, key, kind: str) -> Params:
    ks = jax.random.split(key, 3)
    dt = jnp.dtype(cfg.param_dtype)
    xc = cfg.xlstm
    d_ff = int(xc.ff_mult * cfg.d_model)
    core = (xlstm_lib.mlstm_init(ks[0], xc, dt) if kind == "mlstm"
            else xlstm_lib.slstm_init(ks[0], xc, dt))
    return {"attn_norm": layers.rmsnorm_init(cfg.d_model, dt),
            "core": core,
            "mlp_norm": layers.rmsnorm_init(cfg.d_model, dt),
            "mlp": layers.mlp_init(ks[1], cfg.d_model, d_ff, "gelu", dt)}


# ---------------------------------------------------------- block (apply)

def _pin_batch(cfg: ArchConfig, x: jax.Array) -> jax.Array:
    if cfg.act_batch_axes is None:
        return x
    from jax.sharding import PartitionSpec as P
    return jax.lax.with_sharding_constraint(
        x, P(cfg.act_batch_axes, *([None] * (x.ndim - 1))))


def _block_apply(cfg: ArchConfig, p: Params, x: jax.Array, *,
                 rope_cs, window_enabled=None, cache=None, ssm_state=None,
                 pos=None, block_table=None):
    """Residual block. Returns (x, new_cache, new_ssm_state).

    Attention and the dense MLP run under the named scopes
    ``model.attention`` and ``model.mlp``: each is one ``/`` segment of the
    ``op_name`` of every HLO instruction they produce, in the forward, the
    backward and the remat recompute alike (a profiler trace's per-layer
    split reads them)."""
    x = _pin_batch(cfg, x)
    h = layers.rmsnorm(p["attn_norm"], x, cfg.norm_eps)
    new_cache = new_ssm = None
    with jax.named_scope("model.attention"):
        if cfg.attn_kind == "mla":
            attn_out, new_cache = layers.mla_attention(
                p["attn"], cfg.mla, h, cache=cache, pos=pos, rope_cs=rope_cs,
                block_table=block_table)
        else:
            attn_out, new_cache = layers.attention(
                p["attn"], cfg.attn_cfg(), h, cache=cache, pos=pos,
                rope_cs=rope_cs, window_enabled=window_enabled,
                block_table=block_table)
    if cfg.family == "hybrid":
        ssm_out, new_ssm = ssm_lib.ssm(p["ssm"], cfg.ssm, h, state=ssm_state)
        s = p["mix_scale"].astype(jnp.float32)
        attn_out = (s[0] * attn_out.astype(jnp.float32)
                    + s[1] * ssm_out.astype(jnp.float32)).astype(x.dtype)
    x = x + attn_out
    h = layers.rmsnorm(p["mlp_norm"], x, cfg.norm_eps)
    if cfg.moe is not None:
        x = x + moe_lib.moe(p["moe"], cfg.moe, h)
    else:
        with jax.named_scope("model.mlp"):
            mlp_out = layers.mlp(p["mlp"], h, cfg.act)
        x = x + mlp_out
    return x, new_cache, new_ssm


def _rope_angles(hd: int, positions: jax.Array, theta: float):
    """cos/sin computed directly from (possibly traced) positions — no table,
    so 500k-context decode positions never clip."""
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    return jnp.cos(ang), jnp.sin(ang)


def _rope_for(cfg: ArchConfig, positions: jax.Array):
    """Self-attention rope: new q and new k share the same positions."""
    hd = cfg.mla.qk_rope_dim if cfg.attn_kind == "mla" else cfg.hd
    cos, sin = _rope_angles(hd, positions, cfg.rope_theta)
    return (cos, sin, cos, sin)


def _window_flags(cfg: ArchConfig) -> Optional[jax.Array]:
    if cfg.family != "hybrid" or cfg.sliding_window is None:
        return None
    flags = jnp.ones((cfg.n_layers,), bool)
    for g in cfg.global_layers:
        flags = flags.at[g].set(False)
    return flags


# ------------------------------------------------------------- entry points

def forward(cfg: ArchConfig, params: Params, tokens: jax.Array, *,
            patches: Optional[jax.Array] = None,
            frames: Optional[jax.Array] = None) -> jax.Array:
    """Training forward: (B, S) tokens -> (B, S, vocab) fp32 logits.
    VLM: patch embeds are projected and prepended (logits cover full seq)."""
    x = layers.embed(params["embed"], tokens).astype(
        jnp.dtype(cfg.compute_dtype))
    n_prefix = 0
    if cfg.frontend is not None:
        emb = patches if patches is not None else frames
        fp = params["frontend_proj"]
        pe = layers.linear(fp["fc2"], jax.nn.gelu(
            layers.linear(fp["fc1"], emb.astype(x.dtype))))
        x = jnp.concatenate([pe, x], axis=1)
        n_prefix = pe.shape[1]
    B, S, _ = x.shape

    if cfg.family == "ssm":
        x = _xlstm_forward(cfg, params, x)
    else:
        positions = jnp.arange(S)
        rope_cs = _rope_for(cfg, positions)
        flags = _window_flags(cfg)

        def body(h, scanned):
            bp = scanned[0]
            wf = scanned[1] if flags is not None else None
            h, _, _ = _block_apply(cfg, bp, h, rope_cs=rope_cs,
                                   window_enabled=wf)
            return h, None
        if cfg.remat:
            body = jax.checkpoint(body)
        xs = (params["blocks"],) + ((flags,) if flags is not None else ())
        x, _ = jax.lax.scan(body, x, xs)

    x = layers.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    head = params.get("lm_head", params["embed"])
    logits = layers.unembed(head, x)
    return logits[:, n_prefix:]


def _xlstm_forward(cfg: ArchConfig, params: Params, x: jax.Array) -> jax.Array:
    xc = cfg.xlstm
    every = xc.slstm_every
    n_s = cfg.n_layers // every
    seg = every - 1                       # mLSTM blocks per segment

    def m_body(h, bp):
        h = _xlstm_block(cfg, bp, h, "mlstm")[0]
        return h, None
    if cfg.remat:
        m_body = jax.checkpoint(m_body)

    mb, sb = params["mlstm_blocks"], params.get("slstm_blocks")
    off = 0
    for s_i in range(max(n_s, 1)):
        take = seg if n_s else cfg.n_layers
        blk = jax.tree.map(lambda a: a[off:off + take], mb)
        x, _ = jax.lax.scan(m_body, x, blk)
        off += take
        if n_s and sb is not None:
            one = jax.tree.map(lambda a: a[s_i], sb)
            x = _xlstm_block(cfg, one, x, "slstm")[0]
    # trailing mLSTM blocks, if any
    rest = (cfg.n_layers - n_s) - off
    if rest > 0:
        blk = jax.tree.map(lambda a: a[off:off + rest], mb)
        x, _ = jax.lax.scan(m_body, x, blk)
    return x


def _xlstm_block(cfg: ArchConfig, p: Params, x: jax.Array, kind: str,
                 state=None):
    h = layers.rmsnorm(p["attn_norm"], x, cfg.norm_eps)
    core = xlstm_lib.mlstm if kind == "mlstm" else xlstm_lib.slstm
    out, new_state = core(p["core"], cfg.xlstm, h, state=state)
    x = x + out
    h = layers.rmsnorm(p["mlp_norm"], x, cfg.norm_eps)
    x = x + layers.mlp(p["mlp"], h, "gelu")
    return x, new_state


# ------------------------------------------------------------ serving paths

def init_cache(cfg: ArchConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16):
    """Stacked (L, ...) cache pytree for decode."""
    L = cfg.n_layers
    if cfg.family == "ssm":
        xc = cfg.xlstm
        n_s = L // xc.slstm_every
        return {
            "mlstm": jax.tree.map(
                lambda z: jnp.broadcast_to(z, (L - n_s,) + z.shape).copy(),
                xlstm_lib.mlstm_init_state(xc, batch)),
            "slstm": jax.tree.map(
                lambda z: jnp.broadcast_to(z, (max(n_s, 1),) + z.shape).copy(),
                xlstm_lib.slstm_init_state(xc, batch)),
        }
    cache: Dict[str, Any] = {}
    eff_len = max_len
    if cfg.sliding_window is not None and not cfg.global_layers:
        eff_len = min(max_len, cfg.sliding_window)
    if cfg.attn_kind == "mla":
        cache["ckv"] = jnp.zeros((L, batch, eff_len, cfg.mla.kv_lora_rank),
                                 dtype)
        cache["krope"] = jnp.zeros((L, batch, eff_len, 1,
                                    cfg.mla.qk_rope_dim), dtype)
    else:
        kvshape = (L, batch, eff_len, cfg.n_kv_heads, cfg.hd)
        cache["k"] = jnp.zeros(kvshape, dtype)
        cache["v"] = jnp.zeros(kvshape, dtype)
    if cfg.family == "hybrid":
        conv, h = ssm_lib.ssm_init_state(cfg.ssm, batch)
        cache["conv"] = jnp.broadcast_to(conv, (L,) + conv.shape).copy()
        cache["ssm_h"] = jnp.broadcast_to(h, (L,) + h.shape).copy()
    return cache


# cache leaves carrying a sequence axis — the ones the paged allocator
# (serve/paged.py) stores block-granular; recurrent leaves (conv/ssm_h,
# xLSTM memories) are O(1) per slot and always stay batch-contiguous
PAGED_CACHE_KEYS = ("k", "v", "ckv", "krope")


def init_paged_cache(cfg: ArchConfig, batch: int, num_blocks: int,
                     block_size: int, dtype=jnp.bfloat16):
    """Decode cache whose attention leaves are block pools: (L, P, bs, ...)
    physical blocks shared by every slot through per-request block tables
    (serve/paged.py), instead of a contiguous (L, B, S_max, ...) row per
    slot.  Block 0 is the reserved null block — free slots' idle writes
    land there and no live table ever maps it, so callers size ``P`` as
    ``pool_blocks + 1``.  Recurrent leaves keep the (L, batch, ...) layout
    of init_cache.  Pure-recurrent families (ssm) have no sequence axis to
    page; callers use init_cache unchanged for them."""
    L = cfg.n_layers
    if cfg.family == "ssm":
        raise ValueError("ssm caches are O(1) recurrent state — nothing to "
                         "page; use init_cache")
    if cfg.sliding_window is not None and not cfg.global_layers:
        # the contiguous tier shrinks these caches to a rolling window
        # buffer (init_cache eff_len); paging a rolling buffer would remap
        # physical blocks every window step — not supported
        raise NotImplementedError(
            "paged cache does not cover rolling sliding-window buffers")
    cache: Dict[str, Any] = {}
    if cfg.attn_kind == "mla":
        cache["ckv"] = jnp.zeros((L, num_blocks, block_size,
                                  cfg.mla.kv_lora_rank), dtype)
        cache["krope"] = jnp.zeros((L, num_blocks, block_size, 1,
                                    cfg.mla.qk_rope_dim), dtype)
    else:
        cache["k"] = jnp.zeros((L, num_blocks, block_size,
                                cfg.n_kv_heads, cfg.hd), dtype)
        cache["v"] = jnp.zeros_like(cache["k"])
    if cfg.family == "hybrid":
        conv, h = ssm_lib.ssm_init_state(cfg.ssm, batch)
        cache["conv"] = jnp.broadcast_to(conv, (L,) + conv.shape).copy()
        cache["ssm_h"] = jnp.broadcast_to(h, (L,) + h.shape).copy()
    return cache


def _layer_cache(cfg, cache, sel):
    if cfg.attn_kind == "mla":
        return (cache["ckv"][sel], cache["krope"][sel])
    return (cache["k"][sel], cache["v"][sel])


def _cache_scan(cfg: ArchConfig, params: Params, x: jax.Array, cache, *,
                pos, positions, remat: bool = False, block_tables=None):
    """Scan the blocks threading the decode cache: shared by prefill
    (pos=0), chunked prefill (scalar pos offset) and decode (scalar pos, or
    a (B,) vector of per-slot positions for continuous batching).
    ``block_tables`` (B, W) switches the attention leaves to the paged
    (L, P, bs, ...) pool layout — one table shared by every layer."""
    rope_cs = _rope_for(cfg, positions)
    flags = _window_flags(cfg)

    def body(h, scanned):
        bp, c_l = scanned[0], scanned[1]
        wf = scanned[2] if flags is not None else None
        ssm_state = (c_l.pop("conv"), c_l.pop("ssm_h")) \
            if cfg.family == "hybrid" else None
        kv = tuple(c_l.values())
        h, new_kv, new_ssm = _block_apply(
            cfg, bp, h, rope_cs=rope_cs, window_enabled=wf,
            cache=kv, ssm_state=ssm_state, pos=pos,
            block_table=block_tables)
        out = dict(zip(c_l.keys(), new_kv))
        if new_ssm is not None:
            out["conv"], out["ssm_h"] = new_ssm
        return h, out
    if remat:
        body = jax.checkpoint(body)
    keys = (["ckv", "krope"] if cfg.attn_kind == "mla" else ["k", "v"])
    cdict = {k: cache[k] for k in keys}
    if cfg.family == "hybrid":
        cdict["conv"], cdict["ssm_h"] = cache["conv"], cache["ssm_h"]
    xs = (params["blocks"], cdict) + \
        ((flags,) if flags is not None else ())
    x, new_cache = jax.lax.scan(body, x, xs)
    return x, {**cache, **new_cache}


def prefill(cfg: ArchConfig, params: Params, tokens: jax.Array,
            max_len: int, *, patches: Optional[jax.Array] = None,
            frames: Optional[jax.Array] = None, cache_dtype=jnp.bfloat16):
    """Process the prompt, returning (last-token logits, filled cache).
    VLM/audio-frontend archs prepend the projected patch/frame embeddings;
    the cache then covers prefix + prompt, and decode positions continue at
    ``prefix_len + S``."""
    x = layers.embed(params["embed"], tokens).astype(
        jnp.dtype(cfg.compute_dtype))
    if cfg.frontend is not None:
        emb = patches if patches is not None else frames
        fp = params["frontend_proj"]
        pe = layers.linear(fp["fc2"], jax.nn.gelu(
            layers.linear(fp["fc1"], emb.astype(x.dtype))))
        x = jnp.concatenate([pe, x], axis=1)
    B, S = x.shape[:2]
    cache = init_cache(cfg, B, max_len, cache_dtype)

    if cfg.family == "ssm":
        x, cache = _xlstm_serve(cfg, params, x, cache)
    else:
        x, cache = _cache_scan(cfg, params, x, cache, pos=0,
                               positions=jnp.arange(S), remat=cfg.remat)

    x = layers.rmsnorm(params["final_norm"], x[:, -1:], cfg.norm_eps)
    head = params.get("lm_head", params["embed"])
    return layers.unembed(head, x)[:, 0], cache


def prefill_chunk(cfg: ArchConfig, params: Params, tokens: jax.Array,
                  cache, pos: jax.Array, block_tables=None):
    """Continue a prefill: write a prompt chunk at positions
    [pos, pos + S) of an existing cache (chunked prefill for prompts too
    long to process in one shot — the long_500k serving path).  Token-only:
    frontend archs prepend their prefix in the first full prefill instead.
    ``block_tables`` (1, W): chunk directly into a paged pool cache through
    the request's block table (serve/paged.py admission path).
    Returns (chunk-final logits, cache)."""
    assert cfg.frontend is None, "chunked prefill is token-only"
    x = layers.embed(params["embed"], tokens).astype(
        jnp.dtype(cfg.compute_dtype))
    S = x.shape[1]

    if cfg.family == "ssm":
        x, cache = _xlstm_serve(cfg, params, x, cache)
    else:
        x, cache = _cache_scan(cfg, params, x, cache, pos=pos,
                               positions=pos + jnp.arange(S),
                               remat=cfg.remat, block_tables=block_tables)

    x = layers.rmsnorm(params["final_norm"], x[:, -1:], cfg.norm_eps)
    head = params.get("lm_head", params["embed"])
    return layers.unembed(head, x)[:, 0], cache


def decode_step(cfg: ArchConfig, params: Params, token: jax.Array,
                cache, pos: jax.Array, block_tables=None):
    """One decode step: (B,) token ids + cache + pos -> (logits, cache).
    pos is a scalar (all rows at the same depth) or a (B,) vector of
    per-row positions (slot-based continuous batching).  ``block_tables``
    (B, W) reads/writes the attention cache through per-slot block tables
    over a paged pool (serve/paged.py); recurrent state is unaffected."""
    x = layers.embed(params["embed"], token[:, None]).astype(
        jnp.dtype(cfg.compute_dtype))

    if cfg.family == "ssm":
        x, cache = _xlstm_serve(cfg, params, x, cache)
    else:
        positions = pos[None] if pos.ndim == 0 else pos[:, None]
        x, cache = _cache_scan(cfg, params, x, cache, pos=pos,
                               positions=positions,
                               block_tables=block_tables)

    x = layers.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    head = params.get("lm_head", params["embed"])
    return layers.unembed(head, x)[:, 0], cache


def _xlstm_serve(cfg: ArchConfig, params: Params, x: jax.Array, cache):
    """xLSTM prefill/decode share the recurrent path (state in, state out)."""
    xc = cfg.xlstm
    every = xc.slstm_every
    n_s = cfg.n_layers // every
    seg = every - 1

    def m_body(carry, scanned):
        h = carry
        bp, st = scanned
        h, new_st = _xlstm_block(cfg, bp, h, "mlstm", state=st)
        return h, new_st

    mb, sb = params["mlstm_blocks"], params.get("slstm_blocks")
    m_state, s_state = cache["mlstm"], cache["slstm"]
    new_m, new_s = [], []
    off = 0
    for s_i in range(max(n_s, 1)):
        take = seg if n_s else cfg.n_layers
        blk = jax.tree.map(lambda a: a[off:off + take], mb)
        st = jax.tree.map(lambda a: a[off:off + take], m_state)
        x, st_out = jax.lax.scan(m_body, x, (blk, st))
        new_m.append(st_out)
        off += take
        if n_s and sb is not None:
            one = jax.tree.map(lambda a: a[s_i], sb)
            st1 = jax.tree.map(lambda a: a[s_i], s_state)
            x, st1_out = _xlstm_block(cfg, one, x, "slstm", state=st1)
            new_s.append(jax.tree.map(lambda a: a[None], st1_out))
    rest = (cfg.n_layers - n_s) - off
    if rest > 0:
        blk = jax.tree.map(lambda a: a[off:off + rest], mb)
        st = jax.tree.map(lambda a: a[off:off + rest], m_state)
        x, st_out = jax.lax.scan(m_body, x, (blk, st))
        new_m.append(st_out)
    cache = {
        "mlstm": jax.tree.map(lambda *xs: jnp.concatenate(xs, 0), *new_m),
        "slstm": (jax.tree.map(lambda *xs: jnp.concatenate(xs, 0), *new_s)
                  if new_s else cache["slstm"]),
    }
    return x, cache
