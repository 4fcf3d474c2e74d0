"""Read a cell's comparison on many seeds in one process, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 1 2 3 \\
        [--faults none fp8 half_batch frozen swap bf16 ns_high ...]

For each fault in turn, each seed builds the cell's loop at its own size,
runs its check steps and the reference, and prints one JSON line with every
number of the comparison, compared by the cell or not, and whether the
cell's limits pass it.  ``none`` is the program as it is (the lower readings
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


def control(cell: dict, seed: int) -> dict:
    """The comparison's readings with the reference at float8 products in
    the program's place, on the cell's own check steps."""
    import jax

    from bench.traffic import ZipfTokens
    traffic, conf, ref = cell["traffic"], cell["config"], cell["reference"]
    gen = ZipfTokens(traffic, conf, seed)
    batches = [gen.batch_at(k) for k in range(int(traffic["check_steps"]))]
    mode = traffic["optimizer"].get("mode", "owner")
    devices = jax.devices()[:cell["chips"]]
    low = ref.train_steps(conf, batches, seed, mode, fp8=True,
                          devices=devices)
    low["deltas"] = jax.device_get(low["deltas"])  # off the chip, as the
    # program's are, while the reference runs
    return cell["driver"].compare(low, ref.train_steps(
        conf, batches, seed, mode, devices=devices))


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
    for fault in args.faults:
        for seed in args.seeds:
            t0 = time.perf_counter()
            if fault == "fp8":
                readings = control(cell, seed)
            else:
                out = drv.run(cell, seed, 0.0, False, t0, window=False,
                              fault=None if fault == "none" else fault)
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
