"""Serving example (deliverable b): one-shot batch or continuous batching.

    PYTHONPATH=src python examples/serve_decode.py --arch qwen2.5-14b
    PYTHONPATH=src python examples/serve_decode.py --arch xlstm-350m \
        --mode continuous --requests 12 --rate 50

``--mode oneshot`` (default) is the original static-batch loop: prefill a
batch of prompts together, decode in lockstep, report per-token latency.
``--mode continuous`` drives the same reduced model through the serving
tier (repro.serve): a synthetic request workload flows through the slot
scheduler — insert on free, evict on budget, recycle cache rows — and the
summary reports TTFT / throughput / slot occupancy.
"""

import argparse

import jax

from repro import configs
from repro.models import model_fns
from repro.serve import RequestQueue, Scheduler, ServeConfig, run_oneshot
from repro.launch.compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-14b",
                    choices=list(configs.ARCH_IDS))
    ap.add_argument("--mode", default="oneshot",
                    choices=["oneshot", "continuous"])
    ap.add_argument("--batch", type=int, default=4,
                    help="static batch (oneshot) / decode slots (continuous)")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--requests", type=int, default=12,
                    help="continuous: synthetic workload size")
    ap.add_argument("--rate", type=float, default=None,
                    help="continuous: arrivals/sec (default: all at t=0)")
    ap.add_argument("--kv", default="contiguous",
                    choices=["contiguous", "paged"],
                    help="continuous: cache layout — contiguous per-slot "
                         "rows, or the paged block pool (DESIGN.md §12)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="paged: tokens per cache block")
    ap.add_argument("--pool-blocks", type=int, default=None,
                    help="paged: pool size in blocks (default: same bytes "
                         "as the contiguous reservation)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = configs.get(args.arch, reduced=True)
    m = model_fns(cfg)
    params = jax.jit(lambda k: m.init(cfg, k))(jax.random.PRNGKey(0))
    S = args.prompt_len
    max_len = S + args.new_tokens + 8
    enc_kw = dict(frontend_dim=cfg.frontend_dim) \
        if (cfg.encdec or cfg.frontend is not None) else {}
    if cfg.frontend == "patch":
        # patch prompts carry a fixed image prefix, not per-token frames;
        # the synthetic workload generates frames at frontend geometry
        enc_kw = {}

    if args.mode == "oneshot":
        queue = RequestQueue.synthetic(
            args.batch, cfg.vocab, prompt_lens=(S,),
            new_tokens=(args.new_tokens + 1, args.new_tokens + 1),
            seed=1, **enc_kw)
        queue.poll(0.0)
        reqs = [queue.pop_group(1)[0] for _ in range(len(queue))]
        if cfg.frontend == "patch":
            import numpy as np
            rng = np.random.default_rng(1)
            for r in reqs:
                r.frames = (rng.standard_normal(
                    (cfg.frontend_len, cfg.frontend_dim)) * 0.1
                ).astype(np.float32)
        metrics = run_oneshot(cfg, params, reqs, batch=args.batch,
                              max_len=max_len)
        s = metrics.summary()
        print(f"oneshot: batch={args.batch} prompt={S} "
              f"new={args.new_tokens}")
        print(f"decoded {s['tokens']} tokens in {s['wall_s']:.2f}s "
              f"({s['per_token_ms_median']:.1f} ms/token median, "
              f"incl. compile)")
        rec = next(iter(metrics.requests.values()))
        print("sample token ids:", rec.tokens[:16])
        return

    queue = RequestQueue.synthetic(
        args.requests, cfg.vocab, prompt_lens=(S,),
        new_tokens=(2, args.new_tokens), rate=args.rate, seed=1, **enc_kw)
    scfg = ServeConfig(num_slots=args.batch, max_len=max_len,
                       enc_len=S if cfg.encdec else None,
                       kv=args.kv, block_size=args.block_size,
                       pool_blocks=args.pool_blocks)
    if cfg.frontend == "patch":
        raise SystemExit("continuous mode: patch-frontend archs need "
                         "per-request images; use --mode oneshot")
    if cfg.encdec and args.kv == "paged":
        raise SystemExit("paged KV covers decoder-only archs; enc-dec "
                         "serves with --kv contiguous")
    sched = Scheduler(cfg, params, scfg)
    metrics = sched.run(queue)
    s = metrics.summary()
    print(f"continuous[{args.kv}]: slots={args.batch} "
          f"requests={s['requests']} (rate={args.rate or 'all-at-once'})")
    print(f"  tokens            {s['tokens']}  in {s['wall_s']:.2f}s "
          f"(incl. compile)")
    print(f"  tokens/sec        {s['tokens_per_sec']:.1f}")
    print(f"  ttft ms           {s['ttft_ms_median']:.1f} median / "
          f"{s['ttft_ms_p90']:.1f} p90")
    print(f"  per-token ms      {s['per_token_ms_median']:.1f} median")
    print(f"  decode steps      {s['decode_steps']}  "
          f"(occupancy {s['slot_occupancy']:.2f})")
    if args.kv == "paged":
        print(f"  pool blocks       {s.get('pool_blocks', 0)}  "
              f"(occupancy {s.get('pool_occupancy', 0.0):.2f}, "
              f"frag {s.get('frag_pct', 0.0):.1f}%)")
        print(f"  preemptions       {s['preemptions']}  "
              f"rejected {s['rejected']}")
    rec = next(iter(metrics.requests.values()))
    print("sample token ids:", rec.tokens[:16])


if __name__ == "__main__":
    main()
