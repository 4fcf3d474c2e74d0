"""Read a cell's comparison on many seeds in one process, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 1 2 3 \\
        [--faults none fp8 half_batch frozen swap bf16 ns_high ...]

For each seed in turn the reference runs once; then, for each fault, the
cell's loop is built at its own size and runs its check steps, and one JSON
line gives every number of their comparison with that reference, compared
by the cell or not, and whether the cell's limits pass it.  ``none`` is the program as it is (the lower readings
of PERF.md, section 2).  ``fp8`` is the control: the reference with float8
products put in the program's place.  The others are the driver's
``FAULTS``, planted in the program.  No window runs, nothing is timed, and
the benchmark's own runs never call this.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import time

import run as bench_run   # noqa: E402  (bench/run.py sets up sys.path)


def reference(cell: dict, seed: int, fp8: bool = False) -> dict:
    """The reference's readings on the cell's own check steps from
    ``seed``, its changes copied off the chip (as the program's are);
    ``fp8``: at float8 products, the control."""
    import jax

    from bench.traffic import ZipfTokens
    traffic, conf = cell["traffic"], cell["config"]
    gen = ZipfTokens(traffic, conf, seed)
    batches = [gen.batch_at(k) for k in range(int(traffic["check_steps"]))]
    out = cell["reference"].train_steps(
        conf, batches, seed, traffic["optimizer"].get("mode", "owner"),
        fp8=fp8, devices=jax.devices()[:cell["chips"]])
    out["deltas"] = jax.device_get(out["deltas"])
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--faults", nargs="+", default=["none"])
    args = ap.parse_args()
    cell = bench_run.load_cell(args.workload)
    drv = cell["driver"]
    known = ("none", "fp8") + drv.FAULTS
    if set(args.faults) - set(known):
        ap.error(f"--faults takes {known}")
    import jax
    if jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 3
    bench_run.enable_cache()
    limits = cell["limits"]
    cell["limits"] = {k: limits.get(k, math.inf) for k in drv.CHECKS}
    for seed in args.seeds:
        t0 = time.perf_counter()
        ref = reference(cell, seed)
        print(json.dumps({"cell": args.workload, "seed": seed,
                          "reference_s": round(time.perf_counter() - t0, 1)}),
              flush=True)
        for fault in args.faults:
            t0 = time.perf_counter()
            if fault == "fp8":
                readings = drv.compare(reference(cell, seed, fp8=True), ref)
            else:
                out = drv.run(cell, seed, 0.0, False, t0, window=False,
                              fault=None if fault == "none" else fault,
                              ref=ref)
                readings = {k: v["value"] for k, v in out["checks"].items()}
            correct = all(readings[k] <= v for k, v in limits.items())
            print(json.dumps({"cell": args.workload, "fault": fault,
                              "seed": seed, "correct": correct,
                              "seconds": round(time.perf_counter() - t0, 1),
                              **readings}), flush=True)
            jax.clear_caches()
    return 0


if __name__ == "__main__":
    sys.exit(main())
