"""Benchmark harness (deliverable d): one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run            # all benches
    PYTHONPATH=src python -m benchmarks.run step_time  # one bench
    PYTHONPATH=src python -m benchmarks.run --json .   # also write BENCH_*.json

Prints ``name,us_per_call,derived`` CSV.  Wall-clock rows are measured on
this host (XLA:CPU, 1 device); mesh-scale rows are derived from the measured
cost model and say so in ``derived``.

Suites that expose ``run_records()`` additionally emit versioned
``BENCH_<suite>.json`` files under ``--json DIR`` (schema in
benchmarks/common.py; validated + regression-diffed by
benchmarks/check_regression.py, which CI runs against the committed
baselines).
"""

from __future__ import annotations

import argparse
import os
import sys
import traceback


def main() -> None:
    from benchmarks import (batching, breakdown, load_balance_bench,
                            serve_bench, soak_bench, step_time)
    from benchmarks.common import record_to_csv, write_bench_json
    suites = {
        "step_time": step_time,              # Table 1 / Fig 8
        "breakdown": breakdown,              # Table 2
        "batching": batching,                # Fig 7
        "load_balance": load_balance_bench,  # §3.4
        "serve": serve_bench,                # continuous-batching tier
        "soak": soak_bench,                  # fault-injected resilience drill
    }
    ap = argparse.ArgumentParser()
    ap.add_argument("suite", nargs="*",
                    help=f"suites to run (default: all of {list(suites)})")
    ap.add_argument("--json", metavar="DIR", default=None,
                    help="also write BENCH_<suite>.json files to DIR for "
                         "suites with structured records")
    ap.add_argument("--pipeline", default="both",
                    choices=["fused", "bucketed", "both"],
                    help="optimizer-step schedule(s) for step_time")
    ap.add_argument("--repeats", type=int, default=None,
                    help="wall-clock samples per case for step_time "
                         "(default: the suite's baseline setting)")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    want = args.suite or list(suites)
    unknown = [s for s in want if s not in suites]
    if unknown:
        raise SystemExit(f"unknown suites {unknown}; have {list(suites)}")
    print("name,us_per_call,derived")
    failed = []
    for name in want:
        mod = suites[name]
        try:
            if hasattr(mod, "run_records"):
                kw = {}
                if name == "step_time":
                    kw["pipeline"] = args.pipeline
                    if args.repeats is not None:
                        kw["repeats"] = args.repeats
                records = mod.run_records(**kw)
                for rec in records:
                    print(record_to_csv(rec), flush=True)
                if args.json is not None:
                    path = os.path.join(args.json, f"BENCH_{name}.json")
                    write_bench_json(path, name, records)
                    print(f"# wrote {path}", file=sys.stderr)
            else:
                for row in mod.run():
                    print(row, flush=True)
        except Exception:  # noqa: BLE001 — report per-suite, keep going
            failed.append(name)
            traceback.print_exc()
    if failed:
        raise SystemExit(f"benchmark suites failed: {failed}")


if __name__ == "__main__":
    main()
