"""Serving-step factories: prefill and decode with sharded KV caches.

``make_prefill`` / ``make_decode`` produce the jit-able callables the
dry-run lowers for the prefill_32k / decode_32k / long_500k shapes.  Cache
shardings come from models/sharding.cache_specs (batch over DP, kv-heads
over 'model', sequence over 'model' as the fallback for b=1 long-context).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from repro.models import model_fns, sharding as shard_rules


def prefill_fn(cfg, params, tokens, max_len: int, *,
               cache_dtype=jnp.bfloat16, **kwargs):
    """Functional prefill used by examples and the dry-run step builders."""
    m = model_fns(cfg)
    if cfg.encdec:
        return m.prefill(cfg, params, tokens, frames=kwargs["frames"],
                         max_len=max_len, cache_dtype=cache_dtype)
    if cfg.family == "ssm":
        return m.prefill(cfg, params, tokens, max_len)
    return m.prefill(cfg, params, tokens, max_len,
                     cache_dtype=cache_dtype, **kwargs)


def prefill_chunk_fn(cfg, params, tokens, cache, pos, block_tables=None):
    """Chunked-prefill continuation: write a prompt chunk at [pos, pos+S)
    of an existing cache (serve tier, long-prompt path; token-only).
    ``block_tables`` (1, W) writes the chunk straight into a paged pool
    cache through the request's block table (serve/paged.py)."""
    if cfg.encdec:
        raise NotImplementedError(
            "chunked prefill covers decoder-only families; enc-dec prompts "
            "prefill in one shot")
    m = model_fns(cfg)
    if block_tables is None:
        return m.prefill_chunk(cfg, params, tokens, cache, pos)
    return m.prefill_chunk(cfg, params, tokens, cache, pos,
                           block_tables=block_tables)


def decode_fn(cfg, params, token, cache, pos, block_tables=None):
    """One decode step; ``pos`` is a scalar, or a (B,) vector of per-slot
    positions when driven by the continuous-batching scheduler.  With
    ``block_tables`` (B, W) the attention cache is the paged pool layout
    (serve/paged.py) instead of contiguous per-slot rows."""
    m = model_fns(cfg)
    if block_tables is None:
        return m.decode_step(cfg, params, token, cache, pos)
    return m.decode_step(cfg, params, token, cache, pos,
                         block_tables=block_tables)


def make_cache_shapes(cfg, batch: int, max_len: int,
                      cache_dtype=jnp.bfloat16):
    """ShapeDtypeStructs of the decode cache (no allocation) for dry-runs."""
    m = model_fns(cfg)
    if cfg.encdec:
        fn = lambda: m.init_cache(cfg, batch, max_len, max_len, cache_dtype)
    else:
        fn = lambda: m.init_cache(cfg, batch, max_len, cache_dtype)
    return jax.eval_shape(fn)


def cache_shardings(cfg, cache_shapes, mesh):
    specs = shard_rules.cache_specs(cfg, cache_shapes, mesh)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, PartitionSpec))
