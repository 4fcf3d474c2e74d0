"""Serving-tier tests: slot-parity (bit-identical logits through insert /
evict / recycle), chunked prefill, queue/slot units, and the structural
continuous-vs-oneshot decode-step advantage.

The load-bearing guarantee: a request served through the continuous-batching
scheduler — prefilled packed with strangers, written into a recycled slot
row, decoded in a batch whose other rows sit at different depths — produces
the same greedy tokens as the same prompt run solo through ``prefill_fn`` +
scalar-pos ``decode_fn``, and the same logits up to ``LOGIT_TOL``.  That
holds because slot insertion copies full cache rows and masking never reads
beyond a slot's own position.  float32 caches everywhere (bf16 would round
the reference too and widen the tolerance).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import model_fns
from repro.serve import (Request, RequestQueue, Scheduler, ServeConfig,
                         SlotManager, run_oneshot)
from repro.train import serve as serve_fns

PARITY_ARCHS = ["smollm-360m", "xlstm-350m", "seamless-m4t-large-v2"]

# Served and solo logits come from programs of different shape: a packed
# prefill or a 3-slot decode against batch 1, a chunked prefill against one
# shot.  XLA tiles those dot products differently, so fp32 rounding differs
# in the last bits (observed: a few 1e-8 on logits of order 0.1).  A cache
# row written to the wrong slot, or a mask that reads past a slot's
# position, moves the logits by orders of magnitude more.
LOGIT_TOL = dict(rtol=1e-5, atol=1e-5)


def _build(arch):
    cfg = configs.get(arch, reduced=True)
    m = model_fns(cfg)
    params = jax.jit(lambda k: m.init(cfg, k))(jax.random.PRNGKey(0))
    return cfg, m, params


@pytest.fixture(scope="module", params=PARITY_ARCHS)
def served(request):
    """Run a recycling-heavy workload (7 requests through 3 slots, packed
    prefill, mixed budgets) with logits recording on."""
    cfg, m, params = _build(request.param)
    enc_kw = dict(frontend_dim=cfg.frontend_dim, prompt_lens=(8,)) \
        if cfg.encdec else dict(prompt_lens=(4, 8))
    queue = RequestQueue.synthetic(7, cfg.vocab, new_tokens=(2, 6),
                                   seed=3, **enc_kw)
    reqs = {r.rid: r for r in queue._pending}   # kept for solo replay
    scfg = ServeConfig(num_slots=3, max_len=32, prefill_pack=2,
                       cache_dtype=jnp.float32, record_logits=True,
                       enc_len=8 if cfg.encdec else None)
    sched = Scheduler(cfg, params, scfg)
    metrics = sched.run(queue)
    return cfg, params, scfg, metrics, reqs


def test_slot_parity_bitwise(served):
    """Every served request's token stream equals the same prompt decoded
    solo (batch=1, scalar positions, fresh cache), its logits within
    ``LOGIT_TOL``."""
    cfg, params, scfg, metrics, reqs = served
    assert len(metrics.requests) == 7
    if cfg.encdec:
        prefill = jax.jit(lambda p, t, f: serve_fns.prefill_fn(
            cfg, p, t, scfg.max_len, cache_dtype=jnp.float32, frames=f))
    else:
        prefill = jax.jit(lambda p, t: serve_fns.prefill_fn(
            cfg, p, t, scfg.max_len, cache_dtype=jnp.float32))
    decode = jax.jit(lambda p, t, c, pos: serve_fns.decode_fn(
        cfg, p, t, c, pos))
    prefix = cfg.frontend_len \
        if cfg.frontend is not None and not cfg.encdec else 0

    for rec in metrics.requests.values():
        req = reqs[rec.rid]
        toks = jnp.asarray(req.tokens)[None]
        args = (jnp.asarray(req.frames)[None],) if cfg.encdec else ()
        logits, cache = prefill(params, toks, *args)
        ref = [np.asarray(logits[0])]
        tok = int(np.argmax(ref[0]))
        assert tok == rec.tokens[0], rec.rid
        for i in range(1, rec.generated):
            logits, cache = decode(
                params, jnp.asarray([tok], jnp.int32), cache,
                jnp.asarray(req.prompt_len + prefix + i - 1, jnp.int32))
            ref.append(np.asarray(logits[0]))
            tok = int(np.argmax(ref[-1]))
            assert tok == rec.tokens[i], (rec.rid, i)
        assert len(ref) == len(rec.logits), rec.rid
        for i, (a, b) in enumerate(zip(ref, rec.logits)):
            np.testing.assert_allclose(
                b, a, **LOGIT_TOL,
                err_msg=f"rid {rec.rid} token {i}: served logits != solo")


def test_served_requests_complete(served):
    cfg, params, scfg, metrics, _ = served
    for rec in metrics.requests.values():
        assert rec.generated == rec.requested
        assert rec.t_first is not None and rec.t_done is not None
        assert rec.t_done >= rec.t_first >= rec.arrival


def test_metrics_summary_sane(served):
    cfg, params, scfg, metrics, _ = served
    s = metrics.summary()
    assert s["requests"] == 7
    assert s["tokens"] == sum(r.generated for r in metrics.requests.values())
    assert 0.0 < s["slot_occupancy"] <= 1.0
    assert s["tokens_per_sec"] > 0
    assert s["ttft_ms_p90"] >= s["ttft_ms_median"] >= 0
    assert s["decode_steps"] == len(metrics.decode_step_s)


def test_chunked_prefill_matches_full():
    """prefill_chunk over an existing cache == one-shot prefill, within
    ``LOGIT_TOL``: chunking splits the write schedule (attention) or
    re-associates the scan (xLSTM), so the two programs differ in shape."""
    for arch in ("smollm-360m", "xlstm-350m"):
        cfg, m, params = _build(arch)
        toks = jax.random.randint(jax.random.PRNGKey(7), (1, 12),
                                  0, cfg.vocab)
        max_len = 24
        if cfg.family == "ssm":
            full, _ = m.prefill(cfg, params, toks, max_len)
        else:
            full, _ = m.prefill(cfg, params, toks, max_len,
                                cache_dtype=jnp.float32)
        cache = m.init_cache(cfg, 1, max_len, jnp.float32)
        for off in range(0, 12, 4):
            logits, cache = serve_fns.prefill_chunk_fn(
                cfg, params, toks[:, off:off + 4], cache,
                jnp.asarray(off, jnp.int32))
        np.testing.assert_allclose(np.asarray(logits), np.asarray(full),
                                   **LOGIT_TOL, err_msg=arch)


def test_continuous_beats_oneshot_decode_steps():
    """Structural (count-based, deterministic): on a bimodal-budget
    workload the slot scheduler needs strictly fewer decode steps than
    lockstep rounds at the same batch size."""
    cfg, m, params = _build("smollm-360m")

    def wl():
        return RequestQueue.synthetic(8, cfg.vocab, prompt_lens=(4,),
                                      budgets=(2, 2, 2, 12), seed=5)
    sched = Scheduler(cfg, params, ServeConfig(num_slots=4, max_len=24,
                                               cache_dtype=jnp.float32))
    cont = sched.run(wl()).summary()
    q = wl()
    q.poll(0.0)
    reqs = [q.pop_group(1)[0] for _ in range(len(q))]
    base = run_oneshot(cfg, params, reqs, batch=4, max_len=24,
                       cache_dtype=jnp.float32).summary()
    assert cont["tokens"] == base["tokens"]
    assert cont["decode_steps"] < base["decode_steps"], \
        (cont["decode_steps"], base["decode_steps"])


# ------------------------------------------------------------ queue units

def _req(rid, n, budget=4, arrival=0.0):
    return Request(rid=rid, tokens=np.arange(n, dtype=np.int32),
                   max_new_tokens=budget, arrival=arrival)


def test_queue_packs_equal_lengths_only():
    q = RequestQueue([_req(0, 4), _req(1, 4), _req(2, 8), _req(3, 4)])
    q.poll(0.0)
    g = q.pop_group(3)
    assert [r.rid for r in g] == [0, 1, 3]       # len-8 skipped, kept
    assert [r.rid for r in q.pop_group(3)] == [2]
    assert q.drained


def test_queue_chunked_prompts_go_alone():
    q = RequestQueue([_req(0, 32), _req(1, 32)])
    q.poll(0.0)
    assert [r.rid for r in q.pop_group(4, chunk_len=16)] == [0]
    assert [r.rid for r in q.pop_group(4, chunk_len=16)] == [1]


def test_queue_arrivals_gate_readiness():
    q = RequestQueue([_req(0, 4, arrival=0.5), _req(1, 4, arrival=0.1)])
    assert q.num_ready == 0 and not q.drained
    assert q.next_arrival() == pytest.approx(0.1)
    assert q.poll(0.2) == 1
    assert [r.rid for r in q.pop_group(4)] == [1]
    assert q.poll(1.0) == 1
    assert [r.rid for r in q.pop_group(4)] == [0]


def test_synthetic_deterministic():
    a = RequestQueue.synthetic(5, 100, rate=10.0, seed=9)
    b = RequestQueue.synthetic(5, 100, rate=10.0, seed=9)
    for x, y in zip(a._pending, b._pending):
        assert np.array_equal(x.tokens, y.tokens)
        assert x.arrival == y.arrival and x.max_new_tokens == y.max_new_tokens


# ------------------------------------------------------------- slot units

def test_slot_lifecycle_and_errors():
    cfg, m, params = _build("smollm-360m")
    sm = SlotManager(cfg, 2, max_len=16, cache_dtype=jnp.float32)
    assert sm.num_free == 2 and sm.num_active == 0
    _, rcache = m.prefill(cfg, params,
                          jnp.zeros((1, 4), jnp.int32), 16,
                          cache_dtype=jnp.float32)
    i = sm.insert(_req(0, 4), rcache, 0, first_token=1, pos=4)
    assert sm.num_active == 1 and int(sm.pos[i]) == 4 and int(sm.tok[i]) == 1
    sm.advance(i, 7)
    assert int(sm.pos[i]) == 5 and sm.slots[i].generated == 2
    j = sm.insert(_req(1, 4), rcache, 0, first_token=2, pos=15)
    assert sm.num_free == 0
    assert sm.out_of_cache(j) is False
    sm.advance(j, 3)
    assert sm.out_of_cache(j) is True
    with pytest.raises(RuntimeError):
        sm.insert(_req(2, 4), rcache, 0, first_token=0, pos=4)
    s = sm.evict(i)
    assert s.request.rid == 0 and sm.num_free == 1
    with pytest.raises(ValueError):
        sm.evict(i)
    with pytest.raises(ValueError):
        sm.insert(_req(3, 4), rcache, 0, first_token=0, pos=16)
    # recycled row is claimed again without zeroing
    k = sm.insert(_req(4, 4), rcache, 0, first_token=5, pos=4)
    assert k == i


def test_encdec_slots_require_enc_len():
    cfg, _, _ = _build("seamless-m4t-large-v2")
    with pytest.raises(ValueError, match="enc_len"):
        SlotManager(cfg, 2, max_len=16)


# ---------------------------------------------------------- paged serving

from repro.serve import PagedSlotManager  # noqa: E402
from repro.serve.paged import NULL_BLOCK  # noqa: E402

# the three cache families of DESIGN.md §12: grouped-KV (smollm), MLA
# latent (deepseek), pure-recurrent state (xlstm)
PAGED_PARITY_ARCHS = ["smollm-360m", "deepseek-v3-671b", "xlstm-350m"]


@pytest.fixture(scope="module", params=PAGED_PARITY_ARCHS)
def served_paged(request):
    """The slot-parity workload on the paged allocator, with the
    ``preempt_every`` drill forcing preempt→requeue→resume cycles."""
    cfg, m, params = _build(request.param)
    queue = RequestQueue.synthetic(7, cfg.vocab, prompt_lens=(4, 8),
                                   new_tokens=(2, 6), seed=3)
    reqs = {r.rid: r for r in queue._pending}
    scfg = ServeConfig(num_slots=3, max_len=32, prefill_pack=2,
                       cache_dtype=jnp.float32, record_logits=True,
                       kv="paged", block_size=8, preempt_every=4)
    sched = Scheduler(cfg, params, scfg)
    metrics = sched.run(queue)
    return cfg, params, sched.max_len, metrics, reqs


def test_paged_parity_bitwise(served_paged):
    """Paged serving — block-scattered prefill, gather-indirected decode,
    at least one preempt→resume cycle — matches solo contiguous decode
    (tokens exact, logits within ``LOGIT_TOL``), for KV, MLA and recurrent
    cache families."""
    cfg, params, max_len, metrics, reqs = served_paged
    assert metrics.preemptions >= 1     # the drill actually fired
    assert len(metrics.requests) == 7
    prefill = jax.jit(lambda p, t: serve_fns.prefill_fn(
        cfg, p, t, max_len, cache_dtype=jnp.float32))
    decode = jax.jit(lambda p, t, c, pos: serve_fns.decode_fn(
        cfg, p, t, c, pos))
    for rec in metrics.requests.values():
        req = reqs[rec.rid]
        logits, cache = prefill(params, jnp.asarray(req.tokens)[None])
        ref = [np.asarray(logits[0])]
        tok = int(np.argmax(ref[0]))
        assert tok == rec.tokens[0], rec.rid
        for i in range(1, rec.generated):
            logits, cache = decode(
                params, jnp.asarray([tok], jnp.int32), cache,
                jnp.asarray(req.prompt_len + i - 1, jnp.int32))
            ref.append(np.asarray(logits[0]))
            tok = int(np.argmax(ref[-1]))
            assert tok == rec.tokens[i], (rec.rid, i)
        assert len(ref) == len(rec.logits), rec.rid
        for i, (a, b) in enumerate(zip(ref, rec.logits)):
            np.testing.assert_allclose(
                b, a, **LOGIT_TOL,
                err_msg=f"rid {rec.rid} token {i}: paged logits != solo")


def test_paged_requests_complete(served_paged):
    cfg, params, max_len, metrics, _ = served_paged
    for rec in metrics.requests.values():
        assert rec.generated == rec.requested
        assert not rec.rejected
    s = metrics.summary()
    assert s["preemptions"] >= 1
    if cfg.family != "ssm":
        assert s["pool_blocks"] > 0
        assert 0.0 <= s["pool_occupancy"] <= 1.0


def test_paged_pool_pressure_preempts():
    """An under-provisioned pool (1.5 slots' worth of blocks for 4 slots)
    forces organic preemption — no drill — and every request still
    completes with its full budget."""
    cfg, m, params = _build("smollm-360m")
    queue = RequestQueue.synthetic(8, cfg.vocab, prompt_lens=(4, 8),
                                   new_tokens=(8, 20), seed=5)
    scfg = ServeConfig(num_slots=4, max_len=32, prefill_pack=2,
                       cache_dtype=jnp.float32, kv="paged",
                       block_size=8, pool_blocks=6)
    metrics = Scheduler(cfg, params, scfg).run(queue)
    assert metrics.preemptions >= 1
    for rec in metrics.requests.values():
        assert rec.generated == rec.requested


@pytest.mark.parametrize("kv", ["contiguous", "paged"])
def test_overlength_rejected_gracefully(kv):
    """A prompt that alone fills the cache is rejected at admission —
    recorded done with the ``rejected`` marker — instead of raising out
    of SlotManager.insert; later fitting requests are unaffected."""
    cfg, m, params = _build("smollm-360m")
    q = RequestQueue()
    q.push(_req(0, 40, budget=4))       # 40 >= max_len 32: over-length
    q.push(_req(1, 8, budget=4))
    scfg = ServeConfig(num_slots=2, max_len=32, cache_dtype=jnp.float32,
                       kv=kv, block_size=8)
    metrics = Scheduler(cfg, params, scfg).run(q)
    r0, r1 = metrics.requests[0], metrics.requests[1]
    assert r0.rejected and r0.generated == 0
    assert r0.t_first is None and r0.t_done is not None
    assert not r1.rejected and r1.generated == 4
    assert metrics.summary()["rejected"] == 1


def test_paged_beats_contiguous_concurrency_equal_memory():
    """The headline: at equal cache bytes (12 blocks × 8 tokens), the paged
    tier sustains strictly more concurrent requests than the contiguous
    tier on a bimodal long+short workload, because short requests only
    reserve the blocks they touch."""
    cfg, m, params = _build("smollm-360m")

    def wl():
        return RequestQueue.synthetic(12, cfg.vocab, prompt_lens=(4,),
                                      budgets=(4, 4, 4, 24), seed=11)
    cont = Scheduler(cfg, params, ServeConfig(
        num_slots=3, max_len=32, cache_dtype=jnp.float32)).run(wl())
    paged = Scheduler(cfg, params, ServeConfig(
        num_slots=6, max_len=32, cache_dtype=jnp.float32, kv="paged",
        block_size=8, pool_blocks=12)).run(wl())
    cs, ps = cont.summary(), paged.summary()
    assert ps["tokens"] == cs["tokens"]
    assert ps["concurrent_mean"] > cs["concurrent_mean"], (cs, ps)
    assert ps["decode_steps"] < cs["decode_steps"], (cs, ps)
    for rec in paged.requests.values():
        assert rec.generated == rec.requested


def test_paged_slot_units():
    """PagedSlotManager lifecycle: block accounting across insert /
    advance / evict, table release, null-block invariant."""
    cfg, m, params = _build("smollm-360m")
    sm = PagedSlotManager(cfg, 2, max_len=16, block_size=4,
                          cache_dtype=jnp.float32)
    assert sm.max_len == 16 and sm.blocks_per_slot == 4
    assert sm.pool.num_blocks == 8 and sm.pool.num_free == 8
    _, rcache = m.prefill(cfg, params, jnp.zeros((1, 4), jnp.int32), 16,
                          cache_dtype=jnp.float32)
    i = sm.insert(_req(0, 4), rcache, 0, first_token=1, pos=4)
    assert sm.tables[i].num_blocks == 2          # covers positions 0..4
    assert sm.pool.num_free == 6
    assert NULL_BLOCK not in sm.tables[i].blocks
    bt = sm.block_tables()
    assert bt.shape == (2, 4)
    assert (bt[1 - i] == NULL_BLOCK).all()       # free slot: all-null row
    reserved, used, pool_blocks, used_blocks = sm.pool_stats()
    assert (reserved, used, pool_blocks, used_blocks) == (8, 4, 8, 2)
    sm.evict(i)
    assert sm.pool.num_free == 8 and sm.tables[i] is None
    # exhaustion: two full-length tables drain the pool
    a = sm.insert(_req(1, 4), rcache, 0, first_token=1, pos=15)
    b = sm.insert(_req(2, 4), rcache, 0, first_token=1, pos=11)
    assert sm.pool.num_free == 1
    sm.pos[b] = 15                               # next write needs a block
    preempted = sm.prepare_decode()
    assert [p.request.rid for p in preempted] == []   # 1 free block: fits
    assert sm.pool.num_free == 0
    assert sm.tables[a].num_blocks == 4 and sm.tables[b].num_blocks == 4


def test_paged_prepare_decode_preempts_youngest():
    cfg, m, params = _build("smollm-360m")
    sm = PagedSlotManager(cfg, 2, max_len=16, block_size=4,
                          pool_blocks=5, cache_dtype=jnp.float32)
    _, rcache = m.prefill(cfg, params, jnp.zeros((1, 4), jnp.int32), 16,
                          cache_dtype=jnp.float32)
    a = sm.insert(_req(0, 4), rcache, 0, first_token=1, pos=7)   # 2 blocks
    b = sm.insert(_req(1, 4), rcache, 0, first_token=1, pos=7)   # 2 blocks
    sm.advance(a, 3)                             # pos 8: needs a 3rd block
    sm.advance(b, 3)
    preempted = sm.prepare_decode()
    assert [p.request.rid for p in preempted] == [1]   # youngest evicted
    assert sm.slots[b] is None and sm.num_active == 1
    assert preempted[0].generated == 2 and preempted[0].tokens == [1, 3]
    assert sm.tables[a].num_blocks == 3


def test_paged_encdec_unsupported():
    cfg, _, _ = _build("seamless-m4t-large-v2")
    with pytest.raises(NotImplementedError):
        PagedSlotManager(cfg, 2, max_len=16, enc_len=8)
