"""Resilience soak benchmark: fault-injected drill through the supervised loop.

    PYTHONPATH=src python benchmarks/soak_bench.py --steps 24 --json

Drives ``repro.runtime.resilient.ResilientLoop`` (the production training
supervisor) through a reduced adversity drill — straggler slowdown, owner
kill + re-add, preemption + checkpoint restore — and reports the operational
metrics the resilience story is judged on:

    soak/drill       measured per-step wall time across the whole drill, plus
                     ``recovery_ms`` (median owner-loss/preemption recovery
                     latency) and ``rebalance_ms`` (median online re-plan +
                     state-migration latency) — the soak-suite record shape
                     benchmarks/check_regression.py validates;
    soak/recovery    one derived row per recovery event (kill/readd/preempt);
    soak/rebalance   derived re-plan row with the makespan drop.

Wall-clock numbers are for THIS host (XLA:CPU); the drill itself is the same
script tests/test_resilience.py runs at full length with bit-continuity
assertions.
"""

from __future__ import annotations

import argparse
import os
import sys
import tempfile

if __name__ == "__main__" and __package__ is None:  # direct execution
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from benchmarks.common import record, record_to_csv, write_bench_json

# soak-suite extra fields on measured rows (validated by check_regression)
SOAK_FIELDS = ("recovery_ms", "rebalance_ms")


def _drill_spec(steps: int) -> str:
    """The reduced drill, scaled to ``steps`` (>= 12 for every event to
    land): early slowdown (rebalance), kill + re-add mid-run, preemption
    near the end restoring the latest committed checkpoint."""
    half = steps // 2
    return (f"slow@2:r3x4.0; kill@{half}:r1; readd@{half + 2}; "
            f"preempt@{steps - 2}")


def _median_ms(latencies_s) -> float:
    if not latencies_s:
        return 0.0
    s = sorted(latencies_s)
    mid = len(s) // 2
    med = s[mid] if len(s) % 2 else 0.5 * (s[mid - 1] + s[mid])
    return med * 1e3


def run_records(arch: str = "smollm-360m", steps: int = 24,
                owners: int = 4, seed: int = 0) -> list:
    from repro import configs
    from repro.core.muon import MuonConfig
    from repro.data.pipeline import DataConfig
    from repro.runtime.faults import FaultPlan
    from repro.runtime.resilient import ResilientConfig, ResilientLoop

    if steps < 12:
        raise ValueError(f"drill needs >= 12 steps (got {steps})")
    cfg = configs.get(arch, reduced=True, n_layers=2)
    dcfg = DataConfig(vocab=cfg.vocab, seq_len=16, global_batch=4)
    rcfg = ResilientConfig(steps=steps, ckpt_every=max(4, steps // 4),
                           window=3, cooldown=2, threshold=1.3, seed=seed)
    drill = _drill_spec(steps)

    with tempfile.TemporaryDirectory(prefix="soak_ckpt_") as ckpt_dir:
        loop = ResilientLoop(cfg, dcfg, muon=MuonConfig(), run=rcfg,
                             num_owners=owners, ckpt_dir=ckpt_dir,
                             faults=FaultPlan.parse(drill))
        report = loop.run()

    recovery_ms = _median_ms([r["latency_s"] for r in report.recoveries])
    rebalance_ms = _median_ms([r["latency_s"] for r in report.rebalances])

    rec = record("soak/drill", config=arch, mode="drill",
                 variant=loop.muon_cfg.variant,
                 samples_s=report.step_times)
    rec["recovery_ms"] = recovery_ms
    rec["rebalance_ms"] = rebalance_ms
    rec["derived"] = (f"steps={report.steps} executed={report.executed_steps} "
                      f"recoveries={len(report.recoveries)} "
                      f"rebalances={len(report.rebalances)} "
                      f"drill='{drill}'")
    records = [rec]

    for r in report.recoveries:
        extra = (f"resumed_step={r['resumed_step']}"
                 if r["kind"] == "preempt" else
                 f"owners {r['owners'][0]}->{r['owners'][1]}")
        records.append(record(
            "soak/recovery", config=arch, mode=r["kind"],
            value=r["latency_s"] * 1e3, unit="ms",
            derived=f"step={r['step']} {extra}"))
    for r in report.rebalances:
        records.append(record(
            "soak/rebalance", config=arch, mode="replan",
            value=r["latency_s"] * 1e3, unit="ms",
            derived=(f"step={r['step']} makespan "
                     f"{r['makespan_before_s']:.2e}s -> "
                     f"{r['makespan_after_s']:.2e}s")))
    return records


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--steps", type=int, default=24,
                    help="drill length in training steps (>= 12)")
    ap.add_argument("--owners", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", nargs="?", const=".", default=None,
                    metavar="DIR", help="write BENCH_soak.json to DIR "
                                        "(default: repo root)")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    records = run_records(arch=args.arch, steps=args.steps,
                          owners=args.owners, seed=args.seed)
    print("name,us_per_call,derived")
    for rec in records:
        print(record_to_csv(rec), flush=True)
    if args.json is not None:
        path = os.path.join(args.json, "BENCH_soak.json")
        write_bench_json(path, "soak", records)
        print(f"# wrote {path}", file=sys.stderr)


if __name__ == "__main__":
    main()
