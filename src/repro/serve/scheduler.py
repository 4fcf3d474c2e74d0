"""Continuous-batching scheduler: interleaved prefill + batched decode.

The loop (MaxText ``offline_inference`` style, adapted to this repo's
functional prefill/decode factories in ``train/serve.py``):

    poll queue -> prefill waiting requests into free slots -> one batched
    decode step over ALL slots (per-slot positions) -> sample / advance /
    evict finished -> repeat

Prefill policy: ready requests with the *same* prompt length pack into one
batched prefill call (up to ``prefill_pack``); prompts longer than
``chunk_len`` stream through ``prefill_chunk_fn`` in ``chunk_len``-token
pieces (the long_500k path) and occupy the prefill lane alone.  Decode
runs at the fixed slot batch with the vector-``pos`` decode path, so every
slot advances at its own depth — a slot's token stream is bit-identical to
the same prompt decoded solo (tests/test_serve.py pins this).

``run_oneshot`` is the pre-continuous-batching baseline (the old
``examples/serve_decode.py`` loop): FIFO rounds of ``batch`` requests
prefilled together and decoded in lockstep until the slowest request in
the round finishes — the padding steps it wastes are exactly what slot
recycling reclaims.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import model_fns
from repro.serve.metrics import ServeMetrics
from repro.serve.paged import PagedSlotManager, PreemptedSlot
from repro.serve.queue import Request, RequestQueue
from repro.serve.slots import SlotManager
from repro.train import serve as serve_fns


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Serving-harness knobs (decode batch geometry + prefill policy)."""
    num_slots: int = 8
    max_len: int = 128            # per-slot cache rows (prefix+prompt+new)
    prefill_pack: int = 4         # max equal-length prompts per prefill
    chunk_len: Optional[int] = None   # chunked prefill above this length
    cache_dtype: Any = jnp.bfloat16
    enc_len: Optional[int] = None     # enc-dec: uniform encoder length
    record_logits: bool = False       # keep per-token logits (parity tests)
    # ---- paged KV allocator (serve/paged.py, docs/DESIGN.md §12) ----
    kv: str = "contiguous"            # "contiguous" | "paged"
    block_size: int = 16              # tokens per cache block (paged)
    pool_blocks: Optional[int] = None   # pool size; None = same bytes as
                                        # the contiguous reservation
    watermark: float = 0.05           # free-block fraction held back from
                                      # admission (preemption headroom)
    preempt_every: Optional[int] = None   # drill: force-preempt the
                                          # youngest slot every N decode
                                          # steps (tests; paged mode only)


class Scheduler:
    """One model, one fixed decode batch, many requests."""

    def __init__(self, cfg, params, scfg: ServeConfig = ServeConfig(), *,
                 mesh=None):
        self.cfg = cfg
        self.params = params
        self.scfg = scfg
        self.prefix = (cfg.frontend_len
                       if cfg.frontend is not None and not cfg.encdec else 0)
        if scfg.kv not in ("contiguous", "paged"):
            raise ValueError(f"kv must be contiguous|paged, got {scfg.kv!r}")
        self.paged = scfg.kv == "paged"
        if scfg.preempt_every is not None and not self.paged:
            raise ValueError("preempt_every drills need kv='paged' "
                             "(contiguous slots cannot resume)")
        if self.paged:
            self.slots: SlotManager = PagedSlotManager(
                cfg, scfg.num_slots, scfg.max_len,
                block_size=scfg.block_size, pool_blocks=scfg.pool_blocks,
                cache_dtype=scfg.cache_dtype, enc_len=scfg.enc_len)
        else:
            self.slots = SlotManager(cfg, scfg.num_slots, scfg.max_len,
                                     cache_dtype=scfg.cache_dtype,
                                     enc_len=scfg.enc_len)
        # paged slots round max_len up to block granularity; every staging
        # cache below must match so the gathered sequence length (and hence
        # the logits, bitwise) agrees with the contiguous reference
        self.max_len = self.slots.max_len
        # attention leaves actually pooled?  (pure-recurrent families keep
        # the contiguous cache and only gain preempt/resume machinery)
        self._use_tables = self.paged and self.slots.paged
        if self.paged and scfg.watermark > 0:
            self._wm = max(1, round(scfg.watermark * self.slots.pool.num_blocks))
        else:
            self._wm = 0
        self._resume: List[PreemptedSlot] = []   # preempted, awaiting blocks
        self._steps = 0                          # decode steps (drill clock)
        if mesh is not None:  # pin the slot cache to its serving layout
            if self._use_tables:
                raise NotImplementedError(
                    "paged pool sharding is follow-up work; serve paged "
                    "caches single-process for now")
            self.slots.cache = jax.device_put(
                self.slots.cache,
                serve_fns.cache_shardings(cfg, self.slots.cache, mesh))

        dt = scfg.cache_dtype
        ml = self.max_len
        if cfg.encdec:
            self._prefill = jax.jit(lambda p, t, f: serve_fns.prefill_fn(
                cfg, p, t, ml, cache_dtype=dt, frames=f))
        elif cfg.frontend == "patch":
            self._prefill = jax.jit(lambda p, t, f: serve_fns.prefill_fn(
                cfg, p, t, ml, cache_dtype=dt, patches=f))
        elif cfg.frontend == "frame":
            self._prefill = jax.jit(lambda p, t, f: serve_fns.prefill_fn(
                cfg, p, t, ml, cache_dtype=dt, frames=f))
        else:
            self._prefill = jax.jit(lambda p, t: serve_fns.prefill_fn(
                cfg, p, t, ml, cache_dtype=dt))
        m = model_fns(cfg)
        if not cfg.encdec:
            self._fresh_cache = jax.jit(
                lambda: m.init_cache(cfg, 1, ml, dt))
            self._chunk = jax.jit(
                lambda p, t, c, pos: serve_fns.prefill_chunk_fn(
                    cfg, p, t, c, pos),
                donate_argnums=(2,))
        self._decode = jax.jit(
            lambda p, t, c, pos: serve_fns.decode_fn(cfg, p, t, c, pos),
            donate_argnums=(2,))
        if self._use_tables:
            self._decode_paged = jax.jit(
                lambda p, t, c, pos, bt: serve_fns.decode_fn(
                    cfg, p, t, c, pos, block_tables=bt),
                donate_argnums=(2,))
            # hybrid recurrent leaves sit at the slot batch, so a batch-1
            # chunked prefill cannot stream into the live cache — hybrids
            # stage chunked prompts contiguously and scatter on insert
            self._direct_chunk = cfg.family != "hybrid"
            if self._direct_chunk:
                self._chunk_paged = jax.jit(
                    lambda p, t, c, pos, bt: serve_fns.prefill_chunk_fn(
                        cfg, p, t, c, pos, block_tables=bt),
                    donate_argnums=(2,))
        else:
            self._direct_chunk = False

    # ------------------------------------------------------------- prefill

    def _prefill_group(self, group: List[Request]):
        """Batched prefill of equal-length prompts -> (logits, cache)."""
        toks = jnp.asarray(np.stack([r.tokens for r in group]))
        if self.cfg.encdec or self.cfg.frontend is not None:
            frames = jnp.asarray(np.stack([r.frames for r in group]))
            return self._prefill(self.params, toks, frames)
        return self._prefill(self.params, toks)

    def _prefill_chunked(self, req: Request):
        """Stream one long prompt through the cache in chunk_len pieces."""
        c = self.scfg.chunk_len
        cache = self._fresh_cache()
        toks = np.asarray(req.tokens)[None]
        logits = None
        for off in range(0, req.prompt_len, c):
            logits, cache = self._chunk(
                self.params, jnp.asarray(toks[:, off:off + c]), cache,
                jnp.asarray(off, jnp.int32))
        return logits, cache

    def _prefill_chunked_paged(self, req: Request):
        """Stream one long prompt straight into pool blocks through its
        block table — no contiguous staging cache (the paged long-prompt
        admission path).  Returns (logits, table)."""
        c = self.scfg.chunk_len
        table = self.slots.new_table(req.prompt_len + 1)
        bt = jnp.asarray(table.padded()[None])
        toks = np.asarray(req.tokens)[None]
        logits = None
        for off in range(0, req.prompt_len, c):
            logits, self.slots.cache = self._chunk_paged(
                self.params, jnp.asarray(toks[:, off:off + c]),
                self.slots.cache, jnp.asarray(off, jnp.int32), bt)
        return logits, table

    def _admit(self, group: List[Request], metrics: ServeMetrics,
               t0: float, chunked: bool) -> None:
        table = None
        if chunked and self._direct_chunk:
            logits, table = self._prefill_chunked_paged(group[0])
        elif chunked:
            logits, rcache = self._prefill_chunked(group[0])
        else:
            logits, rcache = self._prefill_group(group)
        first = np.asarray(jnp.argmax(logits, -1), np.int32)
        logits_np = (np.asarray(logits)
                     if self.scfg.record_logits else None)
        now = time.perf_counter() - t0
        metrics.prefill_s.append(now)
        for row, r in enumerate(group):
            pos = r.prompt_len + self.prefix
            if table is not None:
                i = self.slots.insert_prefilled(r, table, int(first[row]),
                                                pos)
            else:
                i = self.slots.insert(r, rcache, row, int(first[row]), pos)
            metrics.on_admit(r, now, int(first[row]),
                             logits_np[row] if logits_np is not None
                             else None)
            if (r.max_new_tokens <= 1
                    or (r.eos_id is not None and first[row] == r.eos_id)):
                metrics.on_done(r.rid, now)
                self.slots.evict(i)

    # ----------------------------------------------------- preempt / resume

    def _requeue(self, ps: PreemptedSlot, metrics: ServeMetrics,
                 t0: float) -> None:
        metrics.on_preempt(ps.request.rid, time.perf_counter() - t0)
        self._resume.append(ps)
        self._resume.sort(key=lambda p: p.seq)   # seniority order

    def _admit_resumes(self, metrics: ServeMetrics, t0: float) -> None:
        """Re-admit preempted requests (before any new admission — they
        hold seniority and already consumed prefill work).  Attention
        families rebuild their cache by re-prefilling prompt + generated
        tokens (bitwise: prefill is chunk-split invariant); recurrent
        families restore the exact saved state rows without recompute."""
        while self._resume and self.slots.num_free > 0:
            ps = self._resume[0]
            r = ps.request
            # tokens the model has consumed so far (the last sampled token
            # has not been fed yet — it is the resumed slot's next input)
            n_fed = r.prompt_len + ps.generated - 1
            pos = n_fed + self.prefix
            if self._use_tables:
                need = self.slots.blocks_for(pos + 1)
                head = 0 if self.slots.num_active == 0 else self._wm
                if self.slots.pool.num_free < need + head:
                    break                     # wait for blocks to free up
            self._resume.pop(0)
            last = int(ps.tokens[-1])
            if not self.slots.paged:
                # pure-recurrent: exact O(1) state restore, no recompute
                self.slots.insert(r, None, 0, last, pos, resume=ps)
                continue
            toks = np.concatenate([
                np.asarray(r.tokens, np.int32),
                np.asarray(ps.tokens[:-1], np.int32)])
            req2 = dataclasses.replace(r, tokens=toks)
            if (self.scfg.chunk_len is not None
                    and len(toks) > self.scfg.chunk_len):
                if self._direct_chunk:
                    _, table = self._prefill_chunked_paged(req2)
                    self.slots.insert_prefilled(r, table, last, pos,
                                                resume=ps)
                    continue
                _, rcache = self._prefill_chunked(req2)
            else:
                _, rcache = self._prefill_group([req2])
            self.slots.insert(r, rcache, 0, last, pos, resume=ps)

    # -------------------------------------------------------------- decode

    def _decode_step(self, metrics: ServeMetrics, t0: float) -> None:
        slots = self.slots
        for i, s in slots.active():     # cache-exhausted: truncate
            if slots.out_of_cache(i):
                metrics.on_done(s.request.rid, time.perf_counter() - t0)
                slots.evict(i)
        if self.paged:
            pe = self.scfg.preempt_every
            if pe and self._steps and self._steps % pe == 0 \
                    and slots.num_active >= 2:
                # drill: force one preempt→requeue→resume cycle (the >=2
                # guard keeps the fleet progressing between drills)
                j = slots._youngest()
                self._requeue(slots.preempt(j), metrics, t0)
            # grow every table to cover its next write; preempt youngest
            # when the pool runs dry
            for ps in slots.prepare_decode():
                self._requeue(ps, metrics, t0)
        n_active = slots.num_active
        if n_active == 0:
            return
        t_start = time.perf_counter()
        if self._use_tables:
            logits, slots.cache = self._decode_paged(
                self.params, jnp.asarray(slots.tok), slots.cache,
                jnp.asarray(slots.pos), jnp.asarray(slots.block_tables()))
        else:
            logits, slots.cache = self._decode(
                self.params, jnp.asarray(slots.tok), slots.cache,
                jnp.asarray(slots.pos))
        nxt = np.asarray(jnp.argmax(logits, -1), np.int32)   # host sync
        self._steps += 1
        metrics.on_decode_step(time.perf_counter() - t_start, n_active)
        metrics.on_pool_sample(*slots.pool_stats())
        logits_np = np.asarray(logits) if self.scfg.record_logits else None
        now = time.perf_counter() - t0
        for i, s in slots.active():
            tok = int(nxt[i])
            slots.advance(i, tok)
            r = s.request
            metrics.on_token(r.rid, tok,
                             logits_np[i] if logits_np is not None else None)
            if (s.generated >= r.max_new_tokens
                    or (r.eos_id is not None and tok == r.eos_id)):
                metrics.on_done(r.rid, now)
                slots.evict(i)

    # ----------------------------------------------------------------- run

    def run(self, queue: RequestQueue) -> ServeMetrics:
        """Serve the queue to completion; returns the metrics sink."""
        metrics = ServeMetrics(self.scfg.num_slots)
        t0 = time.perf_counter()
        while True:
            now = time.perf_counter() - t0
            queue.poll(now)
            self._admit_resumes(metrics, t0)   # preempted hold seniority
            while self.slots.num_free > 0 and queue.num_ready > 0:
                head = queue.peek()
                pos0 = head.prompt_len + self.prefix
                if pos0 >= self.max_len:
                    # over-length: the prompt alone fills the cache.  Reject
                    # at admission (graceful) instead of dying in insert()
                    r = queue.pop_group(1, self.scfg.chunk_len)[0]
                    metrics.on_reject(r, time.perf_counter() - t0)
                    continue
                cap = min(self.slots.num_free, self.scfg.prefill_pack)
                if self._use_tables:
                    # watermark admission: only admit what the free pool
                    # covers, holding back headroom for in-flight growth
                    need = self.slots.blocks_for(pos0 + 1)
                    afford = (self.slots.pool.num_free - self._wm) // need
                    if afford < 1:
                        if (self.slots.num_active == 0
                                and not self._resume
                                and self.slots.pool.num_free >= need):
                            afford = 1     # progress guarantee
                        else:
                            break
                    cap = min(cap, afford)
                group = queue.pop_group(cap, self.scfg.chunk_len)
                chunked = (self.scfg.chunk_len is not None
                           and group[0].prompt_len > self.scfg.chunk_len)
                self._admit(group, metrics, t0, chunked)
            if self.slots.num_active == 0 and not self._resume:
                if queue.drained:
                    break
                nxt = queue.next_arrival()
                if nxt is not None:   # idle until the next arrival
                    time.sleep(min(max(nxt - (time.perf_counter() - t0),
                                       0.0), 0.005))
                continue
            self._decode_step(metrics, t0)
        metrics.wall_s = time.perf_counter() - t0
        return metrics


# ------------------------------------------------------- one-shot baseline

@functools.lru_cache(maxsize=None)
def _oneshot_fns(cfg, max_len: int, dt):
    """jit closures for the baseline, cached so repeated runs (warmup,
    then measurement) hit the same compiled executables."""
    if cfg.encdec or cfg.frontend is not None:
        key = "patches" if cfg.frontend == "patch" else "frames"
        prefill = jax.jit(lambda p, t, f: serve_fns.prefill_fn(
            cfg, p, t, max_len, cache_dtype=dt, **{key: f}))
    else:
        prefill = jax.jit(lambda p, t: serve_fns.prefill_fn(
            cfg, p, t, max_len, cache_dtype=dt))
    decode = jax.jit(lambda p, t, c, pos: serve_fns.decode_fn(
        cfg, p, t, c, pos), donate_argnums=(2,))
    return prefill, decode


def run_oneshot(cfg, params, requests: List[Request], batch: int,
                max_len: int, *, cache_dtype=jnp.bfloat16) -> ServeMetrics:
    """Static-batch baseline: FIFO rounds of ``batch`` requests, each
    prefilled together and decoded in lockstep for the round's largest
    budget.  Requires a uniform prompt length (the old example's setting);
    only requested tokens count toward throughput — the lockstep padding
    is the waste continuous batching removes."""
    lens = {r.prompt_len for r in requests}
    if len(lens) != 1:
        raise ValueError(f"one-shot baseline needs uniform prompts: {lens}")
    prefix = cfg.frontend_len \
        if cfg.frontend is not None and not cfg.encdec else 0
    prefill, decode = _oneshot_fns(cfg, max_len, cache_dtype)

    metrics = ServeMetrics(batch)
    t0 = time.perf_counter()
    for start in range(0, len(requests), batch):
        rnd = requests[start:start + batch]
        S = rnd[0].prompt_len
        toks = jnp.asarray(np.stack([r.tokens for r in rnd]))
        if cfg.encdec or cfg.frontend is not None:
            frames = jnp.asarray(np.stack([r.frames for r in rnd]))
            logits, cache = prefill(params, toks, frames)
        else:
            logits, cache = prefill(params, toks)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        first = np.asarray(tok)
        now = time.perf_counter() - t0
        for row, r in enumerate(rnd):
            metrics.on_admit(r, now, int(first[row]))
            if r.max_new_tokens <= 1:
                metrics.on_done(r.rid, now)
        steps = max(r.max_new_tokens for r in rnd) - 1
        for i in range(steps):
            t_start = time.perf_counter()
            logits, cache = decode(params, tok, cache,
                                   jnp.asarray(S + prefix + i, jnp.int32))
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
            nxt = np.asarray(tok)
            live = [r for r in rnd if r.max_new_tokens > i + 1]
            metrics.on_decode_step(time.perf_counter() - t_start, len(live))
            now = time.perf_counter() - t0
            for row, r in enumerate(rnd):
                if r.max_new_tokens > i + 1:   # still within budget
                    metrics.on_token(r.rid, int(nxt[row]))
                    if r.max_new_tokens == i + 2:
                        metrics.on_done(r.rid, now)
    metrics.wall_s = time.perf_counter() - t0
    return metrics
