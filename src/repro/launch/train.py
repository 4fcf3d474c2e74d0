"""Production training driver on the resilient supervisor loop.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-360m \
        --reduced --seq 128 --steps 50 --opt owner --ckpt-dir /tmp/ckpt

On one chip it trains the full-width config; on the CPU backend use
--reduced (and a short --seq) for the smoke-scale config.  --mesh lays a
(data, model) mesh over every visible device.  The run is supervised
by ``runtime/resilient.py``: streaming deterministic pipeline with a
checkpointable cursor, rotating async checkpoints (train tree + data state),
straggler monitoring with online re-dedication, and elastic recovery from
owner loss / preemption.  ``--faults`` injects a scripted adversity drill
(``runtime/faults.py`` DSL) — the same harness the soak test and
``benchmarks/soak_bench.py`` drive.
"""

from __future__ import annotations

import argparse

import jax
import numpy as np

from repro import configs
from repro.core.gram_ns import GramNSConfig
from repro.core.muon import MuonConfig
from repro.data.pipeline import DataConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.runtime.elastic import remesh
from repro.runtime.faults import FaultPlan
from repro.runtime.resilient import ResilientConfig, ResilientLoop


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list(configs.ARCH_IDS))
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--opt", default="owner",
                    choices=["owner", "gather", "adamw"])
    ap.add_argument("--variant", default="muon",
                    help="optimizer variant (registry in core/api.py)")
    ap.add_argument("--strategy", default="load_balance",
                    choices=["load_balance", "greedy", "lpt", "round_robin",
                             "rank0", "xor"])
    # smollm-360m at full width, 2048 x 4 tokens per step, fits one 16 GiB
    # TPU v5e in owner mode and in adamw mode (the step donates its state)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--lr", type=float, default=0.02)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--pipeline", default="fused",
                    choices=["fused", "bucketed"])
    ap.add_argument("--owners", type=int, default=None,
                    help="owner slots when running without a mesh "
                         "(default: device count)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--mesh", action="store_true",
                    help="build a mesh over all visible devices")
    ap.add_argument("--faults", default=None, metavar="SPEC",
                    help="fault-injection drill, e.g. "
                         "'slow@8:r3x4.0; kill@30:r1; readd@40; preempt@52'")
    ap.add_argument("--no-rebalance", action="store_true",
                    help="disable online straggler re-dedication")
    ap.add_argument("--rebalance-window", type=int, default=20)
    ap.add_argument("--rebalance-threshold", type=float, default=1.3)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = configs.get(args.arch, reduced=args.reduced)
    if cfg.frontend is not None or cfg.encdec:
        raise SystemExit("use examples/serve_decode.py for frontend archs, "
                         "or extend the batch builder with frames/patches")

    mesh = remesh() if args.mesh and len(jax.devices()) > 1 else None
    mcfg = MuonConfig(mode=args.opt, variant=args.variant,
                      learning_rate=args.lr, pipeline=args.pipeline,
                      ns=GramNSConfig())
    rcfg = ResilientConfig(
        steps=args.steps,
        ckpt_every=args.ckpt_every if args.ckpt_dir else 0,
        strategy=args.strategy, accum_steps=args.accum,
        rebalance=not args.no_rebalance, window=args.rebalance_window,
        threshold=args.rebalance_threshold)
    dcfg = DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                      global_batch=args.batch)
    faults = FaultPlan.parse(args.faults) if args.faults else None

    loop = ResilientLoop(
        cfg, dcfg, muon=mcfg, run=rcfg,
        num_owners=args.owners or len(jax.devices()), mesh=mesh,
        ckpt_dir=args.ckpt_dir, faults=faults, resume=args.resume,
        log=lambda *a: print(*a, flush=True))
    print(f"[plan] {loop.plan.stats}")
    if args.resume and int(np.asarray(loop.state.step)):
        print(f"[resume] step {int(np.asarray(loop.state.step))}")

    report = loop.run()
    if report.rebalances:
        print(f"[rebalances] {len(report.rebalances)} "
              f"(last speeds {np.round(report.rebalances[-1]['speed'], 3)})")
    if report.recoveries:
        print(f"[recoveries] "
              f"{[(r['kind'], r['step']) for r in report.recoveries]}")
    print(f"[done] steps={report.steps} owners={report.final_owner_count} "
          f"loss_ema={float(loop.state.loss_ema):.4f} "
          f"avg_step={np.mean(report.step_times)*1e3:.0f} ms")


if __name__ == "__main__":
    main()
