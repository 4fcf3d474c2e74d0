"""Run one benchmark cell on the chips of this machine and print one line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1

The cell is a ``workloads`` entry of ``BENCHMARK.json``.  Everything that
belongs to one configuration, traffic mix or per-layer metric is found by
its name: ``bench/configs/<config>.json`` (which names its plain reference,
``bench/reference/<reference>.py``), ``bench/traffic/<traffic>.json`` (which
names its driver, ``bench/drivers/<driver>.py``), ``bench/limits/<cell>.json``
and ``bench/metrics/<metric>.py``.

With ``--trace 0`` the line carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics read from a profiler trace of the
window.  The run fails, and prints no result, when JAX finds no TPU or fewer
chips than the cell asks for.  The last lines on standard error, and the
``checks`` key of the result line, give each number compared with the
reference beside its limit.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# Import the benchmark as a package from the checkout's root (never this
# directory, whose trace.py would shadow the standard library's), and the
# system under test from src/.
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))


def load_module(path: Path):
    """Import one file of the benchmark by path (names may hold '.')."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str, root: Path = ROOT) -> dict:
    """The cell ``name`` with every file it names, read from ``root``."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    bench = root / "bench"
    config = json.loads((bench / "configs" / f"{w['config']}.json")
                        .read_text())
    traffic = json.loads((bench / "traffic" / f"{w['traffic']}.json")
                         .read_text())

    def applies(m):
        return name in m.get("workloads", [name])

    per_layer = [m for m in spec["per_layer"] if applies(m)]
    return {
        "name": name, "chips": w["chips"], "config": config,
        "traffic": traffic,
        "limits": json.loads((bench / "limits" / f"{name}.json")
                             .read_text())["limits"],
        "end_to_end": [m for m in spec["end_to_end"] if applies(m)],
        "per_layer": per_layer,
        "readers": {m["name"]: load_module(bench / "metrics"
                                           / f"{m['name']}.py")
                    for m in per_layer},
        "reference": load_module(bench / "reference"
                                 / f"{config['reference']}.py"),
        "driver": load_module(bench / "drivers"
                              / f"{traffic['driver']}.py"),
    }


def enable_cache() -> str:
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    where set, else ``.jax_cache/`` at the checkout's root (a fixed path, so
    every run of a checkout after the first loads what the first compiled).
    Every program is kept, however short its compile."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"bench: cell {cell['name']} needs {cell['chips']} TPU "
              f"chip(s); JAX found {len(devices)} {devices[0].platform} "
              f"device(s)", file=sys.stderr)
        return 3
    print(f"[setup] {time.perf_counter() - T0:.1f} s: {len(devices)} "
          f"{devices[0].device_kind}, compile cache {enable_cache()}",
          file=sys.stderr)
    out = cell["driver"].run(cell, args.seed, args.seconds,
                             bool(args.trace), T0)
    checks = out.pop("checks")
    out["checks"] = checks            # the compared numbers come last
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
