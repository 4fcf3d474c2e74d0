"""Serving-tier benchmark: continuous batching vs the one-shot baseline.

    PYTHONPATH=src python benchmarks/serve_bench.py --arch smollm-360m --json

Drives the ``repro.serve`` scheduler over CPU-scale analogues of the three
assigned serving shapes (reduced geometry, same roles):

    prefill_32k  prompt-heavy mix, short budgets      -> TTFT / prefill lane
    decode_32k   uniform short prompts, mixed budgets -> decode throughput;
                 also runs the static-batch one-shot baseline at the same
                 batch size for the head-to-head speedup row
    long_500k    one long prompt, chunked prefill     -> sub-quadratic archs
                 only (same skip rule as the dry-run grid)

Measured rows carry the usual median/p90 decode-step wall time *plus* the
serving fields (``ttft_ms``, ``tokens_per_sec``, ...) — the serve-suite
record shape benchmarks/check_regression.py validates and diffs (throughput
drops are regressions, just like step-time rises).
"""

from __future__ import annotations

import argparse
import os
import sys

if __name__ == "__main__" and __package__ is None:  # direct execution
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from benchmarks.common import record, record_to_csv, write_bench_json

# serve-suite extra fields on measured rows (validated by check_regression)
SERVE_FIELDS = ("ttft_ms", "tokens_per_sec")

# ...and the pool-accounting fields paged rows additionally carry
PAGED_FIELDS = ("pool_blocks", "frag_pct", "preemptions")

# paged cache-block granularity (divides every scenario max_len, so paged
# and contiguous gather the same sequence length — bitwise-equal logits)
BLOCK_SIZE = 16

# CPU-scale stand-ins for the assigned serving shapes: same roles, reduced
# geometry (the real shapes are dry-run lowering targets, not CPU wall
# clock).  `n` scales with --requests except for the long-prompt lane.
SCENARIOS = {
    "prefill_32k": dict(prompt_lens=(24, 32), new_tokens=(2, 6),
                        max_len=48, chunk_len=None),
    "decode_32k": dict(prompt_lens=(8,), new_tokens=(4, 96),
                       budgets=(4, 4, 4, 96), max_len=112,
                       chunk_len=None),
    "long_500k": dict(prompt_lens=(96,), new_tokens=(2, 6),
                      max_len=112, chunk_len=16, n=2),
}

# the headline paged workload: bimodal long+short budgets.  Contiguous
# serves it at `--slots` full-length reservations; paged serves the SAME
# cache bytes (slots * max_len / BLOCK_SIZE blocks) spread over twice the
# decode slots, because short requests only hold the blocks they touch.
MIXED_SCENARIO = dict(prompt_lens=(8,), new_tokens=(4, 64),
                      budgets=(4, 4, 4, 64), max_len=80, chunk_len=None)


def _serve_record(name, *, config, mode, variant, summary):
    rec = record(name, config=config, mode=mode, variant=variant,
                 value=0.0)
    rec["median_us"] = summary["decode_step_us_median"]
    rec["p90_us"] = summary["decode_step_us_p90"]
    rec["samples"] = int(summary["decode_steps"])
    rec["ttft_ms"] = summary["ttft_ms_median"]
    rec["ttft_ms_p90"] = summary["ttft_ms_p90"]
    rec["tokens_per_sec"] = summary["tokens_per_sec"]
    rec["tokens_per_sec_per_chip"] = summary["tokens_per_sec_per_chip"]
    rec["slot_occupancy"] = summary["slot_occupancy"]
    rec["concurrent_mean"] = summary["concurrent_mean"]
    rec["derived"] = (f"tps={summary['tokens_per_sec']:.1f} "
                      f"ttft_ms={summary['ttft_ms_median']:.1f} "
                      f"occ={summary['slot_occupancy']:.2f}")
    if variant == "paged":
        rec["pool_blocks"] = int(summary.get("pool_blocks", 0))
        rec["frag_pct"] = summary.get("frag_pct", 0.0)
        rec["preemptions"] = int(summary.get("preemptions", 0))
        rec["derived"] += (f" pool={rec['pool_blocks']} "
                           f"frag={rec['frag_pct']:.1f}% "
                           f"preempt={rec['preemptions']}")
    return rec


def run_records(arch: str = "smollm-360m", requests: int = 24,
                num_slots: int = 8, seed: int = 0,
                kv: str = "contiguous") -> list:
    from repro import configs
    from repro.configs import shapes
    from repro.models import model_fns
    from repro.serve import (RequestQueue, Scheduler, ServeConfig,
                             run_oneshot)

    cfg = configs.get(arch, reduced=True)
    m = model_fns(cfg)
    params = jax.jit(lambda k: m.init(cfg, k))(jax.random.PRNGKey(0))
    enc_kw = {}
    if cfg.encdec:
        enc_kw = dict(frontend_dim=cfg.frontend_dim)
    variants = {"contiguous": ["continuous"], "paged": ["paged"],
                "both": ["continuous", "paged"]}[kv]
    if cfg.encdec and "paged" in variants:
        variants = [v for v in variants if v != "paged"]

    records = []
    for scen, spec in SCENARIOS.items():
        skip = shapes.cell_supported(cfg, scen)
        if skip is not None:
            records.append(record(f"serve/{scen}", config=arch,
                                  mode=scen, variant="skip",
                                  value=0.0, derived=skip))
            continue
        if cfg.encdec and spec["chunk_len"] is not None:
            records.append(record(f"serve/{scen}", config=arch,
                                  mode=scen, variant="skip", value=0.0,
                                  derived="enc-dec prefills in one shot; "
                                          "no chunked path"))
            continue
        n = spec.get("n", requests)
        if cfg.encdec:  # uniform enc_len across the workload
            spec = dict(spec, prompt_lens=spec["prompt_lens"][:1])

        def workload():
            return RequestQueue.synthetic(
                n, cfg.vocab, prompt_lens=spec["prompt_lens"],
                new_tokens=spec["new_tokens"],
                budgets=spec.get("budgets"), seed=seed, **enc_kw)

        for variant in variants:
            scfg = ServeConfig(num_slots=num_slots,
                               max_len=spec["max_len"],
                               chunk_len=spec["chunk_len"],
                               enc_len=(spec["prompt_lens"][0]
                                        if cfg.encdec else None),
                               kv=("paged" if variant == "paged"
                                   else "contiguous"),
                               block_size=BLOCK_SIZE)
            sched = Scheduler(cfg, params, scfg)
            sched.run(workload())      # warmup: compile everything
            summary = sched.run(workload()).summary()
            records.append(_serve_record(
                f"serve/{scen}", config=arch, mode=scen,
                variant=variant, summary=summary))

            if scen == "decode_32k" and variant == "continuous":
                # head-to-head vs static batching
                q = workload()
                q.poll(0.0)
                reqs = [q.pop_group(1)[0] for _ in range(len(q))]
                run_oneshot(cfg, params, reqs, batch=num_slots,
                            max_len=spec["max_len"])      # warmup
                base = run_oneshot(cfg, params, reqs, batch=num_slots,
                                   max_len=spec["max_len"]).summary()
                records.append(_serve_record(
                    f"serve/{scen}", config=arch, mode=scen,
                    variant="oneshot", summary=base))
                speedup = (summary["tokens_per_sec"]
                           / max(base["tokens_per_sec"], 1e-9))
                records.append(record(
                    "serve/speedup_vs_oneshot", config=arch, mode=scen,
                    value=speedup, unit="ratio",
                    derived=f"continuous/oneshot tokens_per_sec at "
                            f"batch={num_slots}"))

    if "paged" in variants and not cfg.encdec:
        records.extend(_mixed_records(cfg, params, requests=requests,
                                      num_slots=num_slots, seed=seed,
                                      enc_kw=enc_kw))
    return records


def _mixed_records(cfg, params, *, requests, num_slots, seed, enc_kw):
    """The headline paged-vs-contiguous comparison at EQUAL cache bytes:
    bimodal long+short budgets, contiguous at ``num_slots`` full-length
    rows vs paged spreading the same pool over ``2 * num_slots`` slots."""
    from repro.serve import RequestQueue, Scheduler, ServeConfig

    spec = MIXED_SCENARIO
    pool_blocks = num_slots * spec["max_len"] // BLOCK_SIZE

    def workload():
        return RequestQueue.synthetic(
            requests, cfg.vocab, prompt_lens=spec["prompt_lens"],
            new_tokens=spec["new_tokens"], budgets=spec["budgets"],
            seed=seed, **enc_kw)

    out = []
    summaries = {}
    for variant, scfg in [
        ("contiguous", ServeConfig(num_slots=num_slots,
                                   max_len=spec["max_len"])),
        ("paged", ServeConfig(num_slots=2 * num_slots,
                              max_len=spec["max_len"], kv="paged",
                              block_size=BLOCK_SIZE,
                              pool_blocks=pool_blocks)),
    ]:
        sched = Scheduler(cfg, params, scfg)
        sched.run(workload())          # warmup
        summaries[variant] = sched.run(workload()).summary()
        out.append(_serve_record(
            "serve/mixed_long_short", config=cfg.name,
            mode="mixed_long_short", variant=variant,
            summary=summaries[variant]))
    gain = (summaries["paged"]["concurrent_peak"]
            / max(summaries["contiguous"]["concurrent_peak"], 1))
    out.append(record(
        "serve/paged_concurrency_gain", config=cfg.name,
        mode="mixed_long_short", value=gain, unit="ratio",
        derived=f"paged/contiguous peak concurrent requests at equal "
                f"cache bytes ({pool_blocks} blocks x {BLOCK_SIZE} tok); "
                f"mean {summaries['paged']['concurrent_mean']:.1f} vs "
                f"{summaries['contiguous']['concurrent_mean']:.1f}"))
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--requests", type=int, default=24,
                    help="workload size for the mixed-traffic scenarios")
    ap.add_argument("--slots", type=int, default=8,
                    help="decode-batch slots (and one-shot batch size)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--kv", default="both",
                    choices=["contiguous", "paged", "both"],
                    help="cache layout(s) to run: contiguous per-slot "
                         "rows, the paged block pool, or both (paged adds "
                         "the mixed_long_short equal-memory comparison)")
    ap.add_argument("--json", nargs="?", const=".", default=None,
                    metavar="DIR", help="write BENCH_serve.json to DIR "
                                        "(default: repo root)")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    records = run_records(arch=args.arch, requests=args.requests,
                          num_slots=args.slots, seed=args.seed,
                          kv=args.kv)
    print("name,us_per_call,derived")
    for rec in records:
        print(record_to_csv(rec), flush=True)
    if args.json is not None:
        path = os.path.join(args.json, "BENCH_serve.json")
        write_bench_json(path, "serve", records)
        print(f"# wrote {path}", file=sys.stderr)


if __name__ == "__main__":
    main()
