"""The program's own spans: named scopes in the compiled step and the
training loop's host spans in a profiler trace.

Device work is named with ``jax.named_scope``, which becomes one ``/``
segment of each HLO instruction's ``op_name``: ``model.attention`` and
``model.mlp`` in the forward, backward and remat recompute (under ``jvp(``
or ``transpose(``, but for the causal mask), ``dmuon.stage_in``,
``dmuon.orthogonalize`` and ``dmuon.publish`` in the owner update after the
gradient.  Host phases of
``ResilientLoop.run`` are ``TraceAnnotation`` spans on the profiler's clock.
"""

import glob
import re

import jax
import jax.numpy as jnp
import pytest

from repro import configs
from repro.core import api
from repro.core.muon import MuonConfig
from repro.data.pipeline import DataConfig
from repro.models import model_fns
from repro.runtime.faults import FaultInjector, FaultPlan
from repro.runtime.resilient import ResilientConfig, ResilientLoop
from repro.train.step import init_state, make_train_step

MODEL_SCOPES = ("model.attention", "model.mlp")
DMUON_SCOPES = ("dmuon.stage_in", "dmuon.orthogonalize", "dmuon.publish")
OP_NAME = re.compile(r'op_name="([^"]*)"')


def _cfg():
    return configs.get("smollm-360m", reduced=True, n_layers=2)


def _compiled_op_names(muon: MuonConfig, accum_steps: int = 1):
    """The ``op_name`` of every instruction of the compiled train step."""
    cfg = _cfg()
    key = jax.random.PRNGKey(0)
    shapes = jax.eval_shape(lambda k: model_fns(cfg).init(cfg, k), key)
    plan = api.dedicate_params(shapes, num_owners=2, strategy="greedy")
    opt = api.Muon(plan, None, config=muon)
    step = make_train_step(cfg, opt, None, accum_steps=accum_steps)
    state = jax.eval_shape(lambda: init_state(cfg, opt, key))
    tok = jax.ShapeDtypeStruct((4, 16), jnp.int32)
    text = step.lower(state, {"tokens": tok, "labels": tok}).compile() \
        .as_text()
    return OP_NAME.findall(text)


_FUNC = re.compile(r"^\s*func\.func (?:public|private) @([\w.\-]+)")
_CALL = re.compile(r"\bcall @([\w.\-]+)\(")
_LOC_REF = re.compile(r"loc\((#loc\d+)\)\s*$")
_LOC_DEF = re.compile(r'^(#loc\d+) = loc\("([^"]*)"', re.M)


def _mosaic_op_names(text):
    """Op name of each Mosaic call in StableHLO text lowered for a TPU: its
    location's name, after those of the calls that reach its function (XLA
    joins them into the compiled instruction's ``op_name``)."""
    names = dict(_LOC_DEF.findall(text))
    calls, kernels, fn = {}, {}, None
    for line in text.splitlines():
        m = _FUNC.match(line)
        if m:
            fn = m.group(1)
            continue
        ref = _LOC_REF.search(line)
        name = names.get(ref.group(1), "") if ref else ""
        if "tpu_custom_call" in line:
            kernels.setdefault(fn, []).append(name)
        elif _CALL.search(line):
            calls.setdefault(_CALL.search(line).group(1), []).append(
                (fn, name))

    def prefixes(fn):
        if fn == "main":
            return [""]
        return [p + name + "/" for caller, name in calls.get(fn, [])
                for p in prefixes(caller)]
    return [p + n for fn, ns in kernels.items() for n in ns
            for p in prefixes(fn)]


def _with_scope(names, scope):
    return [n for n in names if scope in n.split("/")]


def _in_gradient(name):
    return "jvp(" in name or "transpose(" in name


def _outside_gradient(names):
    """Instructions of the step itself (reducer bodies carry a bare name)
    that lie outside the gradient."""
    return {n for n in names if n.startswith("jit(step)/")
            and not _in_gradient(n)}


# The causal mask depends on no parameter, so the layer scan hoists it out
# of the gradient; it is all a model scope holds there.
MASK_OPS = {"iota", "ge", "broadcast_in_dim"}


@pytest.mark.parametrize("pipeline", ["fused", "bucketed"])
def test_owner_step_carries_every_scope_in_its_class(pipeline):
    names = _compiled_op_names(MuonConfig(mode="owner", pipeline=pipeline))
    for scope in MODEL_SCOPES:
        found = _with_scope(names, scope)
        # forward and backward alike
        assert any("jvp(" in n and "transpose(" not in n for n in found), \
            scope
        assert any("transpose(" in n for n in found), scope
        assert all(n.split("/")[-1] in MASK_OPS
                   for n in _outside_gradient(found)), scope
    for scope in DMUON_SCOPES:
        found = _with_scope(names, scope)
        assert found, scope
        assert not any(_in_gradient(n) for n in found), scope


def test_scopes_are_whole_segments():
    names = _compiled_op_names(MuonConfig(mode="owner"))
    for scope in MODEL_SCOPES + DMUON_SCOPES:
        inside = [n for n in names if scope in n]
        assert inside and inside == _with_scope(names, scope), scope


def test_adamw_step_has_no_dmuon_scope():
    names = _compiled_op_names(MuonConfig(mode="adamw"))
    for scope in DMUON_SCOPES:
        assert not _with_scope(names, scope), scope
    for scope in MODEL_SCOPES:
        assert _with_scope(names, scope), scope


def test_prestaged_accumulation_stages_in_under_its_scope():
    """With accumulation the step packs each microbatch's gradients to the
    owners inside the scan (``BucketPipeline.stage_in``), outside the
    gradient."""
    names = _compiled_op_names(MuonConfig(mode="owner", pipeline="bucketed"),
                               accum_steps=2)
    found = _with_scope(names, "dmuon.stage_in")
    assert found and not any(_in_gradient(n) for n in found)
    assert _with_scope(names, "dmuon.orthogonalize")


def test_attention_kernel_sits_in_the_gradient_under_its_scope():
    """At S = 2048 the step lowered for a TPU runs attention in Mosaic
    kernels (the forward, its remat recompute, dq, dk/dv): each carries
    ``model.attention`` under ``jvp(``/``transpose(``, so ``fwd_bwd_ms``
    counts it and ``optimizer_ms`` does not."""
    cfg = _cfg()
    key = jax.random.PRNGKey(0)
    shapes = jax.eval_shape(lambda k: model_fns(cfg).init(cfg, k), key)
    plan = api.dedicate_params(shapes, num_owners=2, strategy="greedy")
    opt = api.Muon(plan, None, config=MuonConfig(mode="owner"))
    step = make_train_step(cfg, opt, None)
    state = jax.eval_shape(lambda: init_state(cfg, opt, key))
    tok = jax.ShapeDtypeStruct((2, 2048), jnp.int32)
    text = step.trace(state, {"tokens": tok, "labels": tok}).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    names = _mosaic_op_names(text)
    assert len(names) >= 3, names
    for name in names:
        assert "model.attention" in name.split("/"), name
        assert _in_gradient(name), name


def _host_spans(path):
    """``{span name: count}`` over the host planes of an ``.xplane.pb``."""
    counts = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                counts[ev.name] = counts.get(ev.name, 0) + 1
    return counts


def test_loop_spans_in_a_profiler_trace(tmp_path):
    cfg = _cfg()
    loop = ResilientLoop(
        cfg, DataConfig(vocab=cfg.vocab, seq_len=16, global_batch=4),
        muon=MuonConfig(), run=ResilientConfig(steps=1, ckpt_every=2),
        num_owners=2, ckpt_dir=str(tmp_path / "ckpt"))
    loop.run()                          # compile outside the trace
    loop.pipe.seek(1)
    loop.rcfg.steps = 3
    with jax.profiler.trace(str(tmp_path / "trace")):
        report = loop.run()
    assert report.steps == 3 and len(report.step_times) == 3
    assert report.checkpoints == [2]
    path, = glob.glob(f"{tmp_path}/trace/**/*.xplane.pb", recursive=True)
    counts = _host_spans(path)
    # one ``train`` step span and one of each phase per traced step
    for span in ("train", "loop.data", "loop.step", "loop.report"):
        assert counts.get(span) == 2, (span, counts.get(span))
    assert counts.get("loop.checkpoint") == 1
    assert "loop.recover" not in counts


def test_recovery_runs_in_its_span(tmp_path):
    cfg = _cfg()
    loop = ResilientLoop(
        cfg, DataConfig(vocab=cfg.vocab, seq_len=16, global_batch=4),
        muon=MuonConfig(), run=ResilientConfig(steps=3), num_owners=2,
        faults=FaultPlan.parse("kill@1:r1,readd@2"))
    loop.run()                          # compile both owner counts untraced
    loop.injector = FaultInjector(FaultPlan.parse("kill@3:r1,readd@4"))
    loop.pipe.seek(3)
    loop.rcfg.steps = 5
    with jax.profiler.trace(str(tmp_path)):
        report = loop.run()
    assert [r["kind"] for r in report.recoveries[-2:]] == ["kill", "readd"]
    path, = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    counts = _host_spans(path)
    assert counts.get("loop.recover") == 2
    assert counts.get("train") == counts.get("loop.step") == 2
