"""The comparison that decides ``correct``, driven through a whole run of a
training cell on the CPU at a tiny size, with the look for a chip skipped:
the program as it is passes, and each fault a one-chip training cell can
have makes ``correct`` false (a state left unchanged, half of the batch left
out), as do a misrouted update that keeps every norm and Gram Newton-Schulz
in bfloat16; so does the control, the float8 reference in the program's
place.  Also: ``run.py`` itself prints no result without a TPU, or without
the program beside the benchmark.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

from bench.run import ROOT, load_cell

DATA = Path(__file__).resolve().parent / "data"

# Limits for the tiny configuration on the CPU, where the program computes
# in full float32: its gaps to the reference read at most 1.4e-5 (loss),
# 8e-7 (gradient norm), 8e-6 (change norm) and 3.2e-4 (change direction:
# the program's Gram-space iteration against the plain one), seed 2**31 + 7.
# The chip cells' own limits are in bench/limits/.
TINY_LIMITS = {"loss_gap": 1e-4, "grad_norm_gap": 1e-4,
               "update_norm_gap": 1e-4, "update_dir_gap": 2e-3}


def tiny_cell(mode="owner"):
    cell = load_cell("smollm-360m.muon-4x2048")
    cell["config"] = json.loads((DATA / "tiny.json").read_text())
    cell["traffic"] = dict(
        cell["traffic"], batch=4, seq_len=64,
        optimizer=dict(cell["traffic"]["optimizer"], mode=mode))
    cell["limits"] = TINY_LIMITS
    return cell


@pytest.mark.parametrize("mode,fault,correct", [
    (mode, fault, correct) for mode in ("owner", "adamw")
    for fault, correct in ((None, True), ("half_batch", False),
                           ("frozen", False), ("swap", False))
] + [("owner", "ns_bf16", False)])
def test_correct_sees_each_fault(mode, fault, correct):
    cell = tiny_cell(mode)
    out = cell["driver"].run(cell, 2**31 + 7, 0.2, False, time.perf_counter(),
                             fault=fault)
    assert out["correct"] is correct, out["checks"]
    assert list(out)[-1] == "checks" and set(out["checks"]) == set(
        TINY_LIMITS)
    assert set(out["metrics"]) == {"tokens_per_s", "setup_s"}
    assert out["attempted"] >= 1 and out["failed"] == 0


def test_float8_reference_fails_as_the_control():
    """The control: the reference at float8 products, in the program's
    place, reads above the limits."""
    from bench.traffic import ZipfTokens
    cell = tiny_cell()
    ref, drv = cell["reference"], cell["driver"]
    gen = ZipfTokens(cell["traffic"], cell["config"], 7)
    batches = [gen.batch_at(k) for k in range(3)]
    readings = drv.compare(
        ref.train_steps(cell["config"], batches, 7, "owner", fp8=True),
        ref.train_steps(cell["config"], batches, 7, "owner"))
    assert any(v > TINY_LIMITS[k] for k, v in readings.items()), readings


FOUR = """
import sys, time
import jax
from bench.tests.test_checks import TINY_LIMITS, tiny_cell
from bench.traffic import ZipfTokens
for fault, want in ((None, True), ("half_batch", False), ("frozen", False),
                    ("swap", False), ("shard_ns", False)):
    cell = tiny_cell()
    cell["chips"] = 4
    out = cell["driver"].run(cell, 2**31 + 7, 0.2, False, time.perf_counter(),
                             fault=fault)
    assert out["correct"] is want, (fault, out["checks"])
    assert out["device"]["count"] == 4
    print(fault, {k: v["value"] for k, v in out["checks"].items()})
ref, drv = cell["reference"], cell["driver"]
gen = ZipfTokens(cell["traffic"], cell["config"], 7)
batches = [gen.batch_at(k) for k in range(3)]
readings = drv.compare(
    ref.train_steps(cell["config"], batches, 7, "owner", fp8=True,
                    devices=jax.devices()),
    ref.train_steps(cell["config"], batches, 7, "owner",
                    devices=jax.devices()))
assert any(v > TINY_LIMITS[k] for k, v in readings.items()), readings
print("four devices ok")
"""


def test_a_four_device_cell_runs_on_a_mesh_and_a_split_reference():
    """A cell on four devices: the program on ``remesh()``'s (1, 4) mesh
    with four owners, the reference split over the same four devices.  The
    program as it is passes; each fault a four-chip training cell can have
    makes ``correct`` false (a state left unchanged, half of the batch left
    out, the exchange between chips left out: ``shard_ns``), as do a
    misrouted update and the float8 control on the split reference."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT), str(ROOT / "src")]))
    r = subprocess.run([sys.executable, "-c", FOUR], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0 and "four devices ok" in r.stdout, r.stderr[-3000:]


@pytest.mark.parametrize("name", [c["name"] for c in json.loads(
    (ROOT / "BENCHMARK.json").read_text())["configs"]])
def test_each_configuration_is_the_programs(name):
    """Each configuration file gives the program's own sizes, and lists
    every key it changes from its source, with the published value."""
    from bench.drivers.train import program_config
    spec = {c["name"]: c for c in json.loads(
        (ROOT / "BENCHMARK.json").read_text())["configs"]}[name]
    conf = json.loads((ROOT / spec["file"]).read_text())
    program_config(conf, None)
    assert conf["source"] == spec["source"]
    assert conf["reduced"] == spec["reduced"]
    assert sorted(conf.get("published", {})) == sorted(spec["reduced"])


def run_py(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "smollm-360m.muon-4x2048", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_no_result_without_a_tpu():
    r = run_py(ROOT)
    assert r.returncode != 0 and r.stdout.strip() == ""
    assert "needs 1 TPU" in r.stderr


def test_no_result_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = run_py(tmp_path)
    assert r.returncode != 0 and r.stdout.strip() == ""
