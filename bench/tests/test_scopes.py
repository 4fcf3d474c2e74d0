"""Device time under the program's named scopes (``bench/scopes.py``) and
the four readers of it, on hand-made instructions and on a small trace
recorded on a TPU v5e from a program without scopes.  CPU only.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests
"""

from __future__ import annotations

from pathlib import Path

import pytest

from bench import scopes, trace as tr
from bench.run import ROOT, load_module

DATA = Path(__file__).resolve().parent / "data"
READERS = ("orthogonalize_ms", "owner_exchange_ms", "attention_ms",
           "mlp_ms")

# op_name paths as the compiled step carries them: forward, backward and
# remat recompute of a decoder block, and the owner update after the
# gradient.
NAMES = {
    "fusion.1": "jit(step)/jvp()/while/body/closed_call/model.attention/"
                "bgrst,btgh->bsgrh/dot_general",
    "fusion.2": "jit(step)/transpose(jvp())/while/body/closed_call/"
                "model.attention/dot_general",
    "fusion.3": "jit(step)/jvp()/while/body/closed_call/checkpoint/"
                "rematted_computation/model.attention/exp",
    "fusion.4": "jit(step)/transpose(jvp())/while/body/closed_call/"
                "model.mlp/dot_general",
    "fusion.5": "jit(step)/jvp()/while/body/closed_call/model.mlp/jit(silu)",
    "fusion.6": "jit(step)/dmuon.stage_in/add",
    "fusion.7": "jit(step)/dmuon.orthogonalize/dot_general",
    "fusion.8": "jit(step)/dmuon.orthogonalize/jit(_gram)/dot_general",
    "fusion.9": "jit(step)/dmuon.publish/mul",
    # scope names inside other segments, or other words, do not count
    "fusion.10": "jit(step)/jvp()/model.attention_cache/add",
    "fusion.11": "jit(step)/jvp()/my.model.mlp/add",
    "fusion.12": "jit(step)/transpose(model.attention)/add",
    "fusion.13": "jit(step)/jvp()/while/body/closed_call/rmsnorm/mul",
}
OPS = {f"fusion.{i}": float(10 * i) for i in range(1, 14)}
OPS["copy.1"] = 1000.0                  # not an instruction of the step


@pytest.fixture(scope="module")
def readers():
    return {n: load_module(ROOT / "bench" / "metrics" / f"{n}.py")
            for n in READERS}


def test_scope_is_a_whole_segment():
    assert scopes.scope_ns(OPS, NAMES, "model.attention") == 10 + 20 + 30
    assert scopes.scope_ns(OPS, NAMES, "model.mlp") == 40 + 50
    assert scopes.scope_ns(OPS, NAMES, "dmuon.orthogonalize") == 70 + 80
    assert scopes.scope_ns(OPS, NAMES, "attention") == 0
    assert scopes.scope_ns(OPS, NAMES, "model") == 0


def test_several_scopes_count_an_instruction_once():
    names = dict(NAMES, **{"fusion.1": "jit(step)/dmuon.stage_in/"
                                       "dmuon.publish/add"})
    assert scopes.scope_ns(OPS, names, "dmuon.stage_in",
                           "dmuon.publish") == 10 + 60 + 90


def ctx_of(ops_per_device, names=None, steps=2):
    ctx = {"steps": steps,
           "trace": {d: {"ops": ops} for d, ops in ops_per_device.items()}}
    if names is not None:
        ctx["op_names"] = names
    return ctx


def test_readers_per_step_mean_over_chips(readers):
    ctx = ctx_of({"/device:TPU:0": OPS,
                  "/device:TPU:1": {k: 3 * v for k, v in OPS.items()}},
                 NAMES)
    per_step = {n: readers[n].read(ctx) for n in READERS}
    # mean of 1x and 3x over 2 steps, ns to ms
    scale = 2.0 / 2 * 1e-6
    assert per_step == pytest.approx({
        "orthogonalize_ms": 150 * scale, "owner_exchange_ms": 150 * scale,
        "attention_ms": 60 * scale, "mlp_ms": 90 * scale})


def test_readers_read_none_without_their_scope(readers):
    plain = {k: "jit(step)/jvp()/while/body/add" for k in NAMES}
    for name in READERS:
        assert readers[name].read(ctx_of({"/device:TPU:0": OPS},
                                         plain)) is None
        assert readers[name].read({"steps": 2, "op_names": NAMES}) is None
    adamw = {k: v for k, v in NAMES.items() if "dmuon." not in v}
    ctx = ctx_of({"/device:TPU:0": OPS}, adamw)
    assert readers["orthogonalize_ms"].read(ctx) is None
    assert readers["owner_exchange_ms"].read(ctx) is None
    assert readers["attention_ms"].read(ctx) > 0


def test_scope_present_but_idle_reads_zero(readers):
    ctx = ctx_of({"/device:TPU:0": {"fusion.13": 5.0}}, NAMES)
    assert readers["mlp_ms"].read(ctx) == 0.0


def test_readers_read_none_without_the_map(readers):
    """Self time per instruction alone names no layer: without the step's
    ``op_names`` map the readers read None, not 0."""
    ctx = ctx_of({"/device:TPU:0": OPS})
    for name in READERS:
        assert readers[name].read(ctx) is None


def test_recorded_trace_of_a_program_without_scopes(readers):
    """A v5e trace of a step with no named scopes reads None, as a parent
    commit without them does."""
    devices, _ = tr.load(str(DATA / "tiny_v5e.xplane.pb"))
    names = tr.op_names((DATA / "tiny_v5e_hlo.txt").read_text())
    red = {d: tr.reduce_device(evs, lambda n, s: None)
           for d, evs in devices.items()}
    ctx = {"steps": 3, "trace": red, "op_names": names}
    assert sum(red["/device:TPU:0"]["ops"].values()) > 0
    for name in READERS:
        assert readers[name].read(ctx) is None
