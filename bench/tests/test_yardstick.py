"""The benchmark's own arithmetic: FLOP and byte counts against hand counts,
the trace reduction on hand-made events and on a small trace recorded on a
TPU v5e, and the traffic generator.  CPU only.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np
import pytest

from bench import flops, trace as tr
from bench.traffic import ZipfTokens

BENCH = Path(__file__).resolve().parents[1]
DATA = Path(__file__).resolve().parent / "data"


def config(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def test_smollm_model_flops_hand_count():
    cfg = config("smollm-360m")
    # per layer: q 960x960, k and v 960x320, o 960x960, gate/up/down 960x2560
    layer = 921_600 + 2 * 307_200 + 921_600 + 3 * 2_457_600
    n_matmul = 32 * layer + 49_152 * 960          # + the tied head
    assert flops.matmul_params(cfg) == n_matmul == 361_758_720
    attn = 12 * 32 * 15 * 64 * 2048
    assert flops.model_flops_per_step(cfg, 4, 2048) == \
        8192 * (6 * n_matmul + attn)


def test_param_count_matches_program_init():
    import jax

    from repro import configs
    from repro.models import model_fns
    for name, arch in (("smollm-360m", configs.get("smollm-360m")),
                       ("qwen2.5-14b-1L", configs.get(
                           "qwen2.5-14b", n_layers=1, vocab=19008))):
        shapes = jax.eval_shape(lambda k: model_fns(arch).init(arch, k),
                                jax.random.PRNGKey(0))
        n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
        assert flops.param_count(config(name)) == n


def test_gram_ns_flops_hand_count():
    # (960, 2560), 5 steps: half a SYRK 2*960^2*2560/2, 17 symmetric
    # 960^3 products at half of 2*960^3, and the 960x960 @ 960x2560 product
    assert flops.gram_ns_flops(960, 2560) == \
        2_359_296_000 + 17 * 884_736_000 + 4_718_592_000
    assert flops.gram_ns_flops(2560, 960) == flops.gram_ns_flops(960, 2560)


def test_optimizer_bytes():
    cfg = config("smollm-360m")
    n_all = flops.param_count(cfg)
    n_mat = 32 * sum(m * n for m, n in flops.layer_matrices(cfg))
    muon = flops.optimizer_work(cfg, "owner")
    adam = flops.optimizer_work(cfg, "adamw")
    assert muon["bytes"] == 4 * (3 * n_all + 2 * n_mat + 4 * (n_all - n_mat))
    assert adam["bytes"] == 4 * 7 * n_all and adam["flops"] == 0


def ev(name, s, e, **stats):
    return (name, float(s), float(e), stats)


def kinds_of(events):
    """``collective_kinds`` of an HLO module that holds an instruction for
    each hand-made event, its opcode the event's name less its number
    (``all-gather-start.4`` is an ``all-gather-start``)."""
    body = "".join(f"  %{n} = f32[8]{{0}} {n.rsplit('.', 1)[0]}(%a)\n"
                   for n in sorted({e[0] for e in events}))
    return tr.collective_kinds(
        "HloModule m\n\nENTRY %main (a: f32[8]) -> f32[8] {\n"
        f"  %a = f32[8]{{0}} parameter(0)\n{body}}}\n")


def test_self_times_and_union():
    events = [ev("while", 0, 100), ev("a", 10, 40), ev("b", 50, 60),
              ev("c", 120, 130)]
    own = {e[0]: t for e, t in tr.self_times(events)}
    assert own == {"while": 60.0, "a": 30.0, "b": 10.0, "c": 10.0}
    assert tr.union([(0, 10), (5, 20), (30, 40)]) == [(0, 20), (30, 40)]


def test_reduce_device_classes_and_exposed_collectives():
    events = [ev("fusion.1", 0, 100), ev("all-to-all.2", 80, 150),
              ev("fusion.3", 200, 250), ev("all-gather-start.4", 240, 300)]
    kinds = kinds_of(events)
    assert kinds == {"all-to-all.2": "all-to-all",
                     "all-gather-start.4": "all-gather"}
    red = tr.reduce_device(events, lambda n, s: "x" if "fusion" in n else
                           "y", kinds)
    assert red["busy_ns"] == 250.0          # [0, 150] and [200, 300]
    assert red["class_ns"] == {"x": 150.0, "y": 130.0}
    assert red["exposed_ns"] == 50.0 + 50.0  # [100, 150] and [250, 300]
    assert red["exposed_by"] == {"all-to-all": 50.0, "all-gather": 50.0}
    assert red["gaps"] == [(150.0, 200.0)]


# The shapes of the TPU compiler's collectives in a compiled step: an
# asynchronous all-gather as start and done fusions that compute nothing
# else, a collective matmul (a product with an all-gather inside), and
# plain and split collectives.
HLO = """HloModule step

%fused_computation.1 (param_0: f32[8]) -> f32[32] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %all-gather.3 = f32[32]{0} all-gather(%param_0), dimensions={0}
}

%async_collective_fusion.2 (p0: f32[8], p1: f32[32,32]) -> (f32[32], f32[32,32]) {
  %p0 = f32[8]{0} parameter(0)
  %p1 = f32[32,32]{1,0} parameter(1)
  %all-gather.4 = f32[32]{0} all-gather(%p0), dimensions={0}
  %convolution.5 = f32[32,32]{1,0} convolution(%p1, %p1), dim_labels=bf_io->bf
  ROOT %tuple.6 = (f32[32]{0}, f32[32,32]{1,0}) tuple(%all-gather.4, %convolution.5)
}

ENTRY %main.7 (a: f32[8], b: f32[32,32]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %b = f32[32,32]{1,0} parameter(1)
  %async-collective-start = (f32[8]{0}, f32[32]{0:T(8)S(1)}) fusion(%a), kind=kCustom, calls=%fused_computation.1
  %fusion.7 = (f32[32]{0}, f32[32,32]{1,0}) fusion(%a, %b), kind=kOutput, calls=%async_collective_fusion.2
  %all-to-all.8 = f32[8]{0} all-to-all(%a), dimensions={0}
  %all-reduce-start.9 = f32[8]{0} all-reduce-start(%a), to_apply=%add
  %all-reduce-done.10 = f32[8]{0} all-reduce-done(%all-reduce-start.9)
  ROOT %add.11 = f32[8]{0} add(%a, %a)
}
"""


def test_collective_kinds_by_opcode():
    assert tr.collective_kinds(HLO) == {
        "all-gather.3": "all-gather", "all-gather.4": "all-gather",
        "async-collective-start": "all-gather", "all-to-all.8": "all-to-all",
        "all-reduce-start.9": "all-reduce", "all-reduce-done.10": "all-reduce"}


def test_a_loop_hides_no_collective():
    """A collective inside a loop's op is exposed where no op beside it
    computes."""
    events = [ev("while.1", 0, 300), ev("fusion.2", 0, 100),
              ev("async-collective-start", 100, 150), ev("fusion.4", 150, 300)]
    red = tr.reduce_device(events, lambda n, s: None,
                           tr.collective_kinds(HLO))
    assert red["exposed_by"] == {"all-gather": 50.0}
    assert red["exposed_ns"] == 50.0


def test_exposed_collective_readers():
    """``alltoall_exposed_ms`` and ``collective_exposed_ms`` on two chips'
    reduced traces: collectives overlapped by compute in part, in whole
    and not at all; per step, mean over chips, ns to ms."""
    from bench.run import load_module
    read = {n: load_module(BENCH / "metrics" / f"{n}.py").read
            for n in ("alltoall_exposed_ms", "collective_exposed_ms")}
    chip0 = [ev("fusion.1", 0, 100), ev("all-to-all.2", 80, 150),
             ev("fusion.3", 200, 250), ev("all-gather-start.4", 240, 300),
             ev("all-to-all.5", 400, 460), ev("fusion.6", 430, 440),
             ev("fusion.9", 495, 510), ev("all-reduce.8", 500, 520),
             ev("fusion.10", 510, 525)]
    chip1 = [ev("all-to-all.2", 0, 40), ev("reduce-scatter.7", 50, 70)]
    kinds = kinds_of(chip0 + chip1)
    red = {d: tr.reduce_device(evs, lambda n, s: None, kinds)
           for d, evs in (("/device:TPU:0", chip0),
                          ("/device:TPU:1", chip1))}
    # chip 0: all-to-all 50 + 50 exposed, all-gather 50, all-reduce hidden
    assert red["/device:TPU:0"]["exposed_by"] == {
        "all-to-all": 100.0, "all-gather": 50.0, "all-reduce": 0.0}
    ctx = {"steps": 2, "trace": red}
    assert read["alltoall_exposed_ms"](ctx) == pytest.approx(
        (100 + 40) / 2 / 2 * 1e-6)
    assert read["collective_exposed_ms"](ctx) == pytest.approx(
        (150 + 60) / 2 / 2 * 1e-6)
    # no all-to-all on any chip: only the other reader reads
    ctx["trace"] = {d: tr.reduce_device(
        [e for e in evs if "all-to-all" not in e[0]], lambda n, s: None,
        kinds)
        for d, evs in (("/device:TPU:0", chip0), ("/device:TPU:1", chip1))}
    assert read["alltoall_exposed_ms"](ctx) is None
    assert read["collective_exposed_ms"](ctx) == pytest.approx(
        (50 + 20) / 2 / 2 * 1e-6)
    # one chip with no collective, and a run without a trace: None
    one = {"steps": 2, "trace": {"/device:TPU:0": tr.reduce_device(
        [ev("fusion.1", 0, 100)], lambda n, s: None, kinds)}}
    for r in read.values():
        assert r(one) is None
        assert r({"steps": 2}) is None


def test_label_gaps_names_the_covering_host_span():
    hosts = {"/host:CPU/main": [ev("whole", 0, 1000), ev("bench.feed", 140,
                                                         210)]}
    labels = tr.label_gaps([(150.0, 200.0), (300.0, 305.0)], hosts,
                           (0.0, 1000.0))
    assert [n for n, _ in labels] == ["bench.feed", "no host span"]
    assert [t for _, t in labels] == pytest.approx([50e-9, 5e-9])


def test_recorded_tpu_trace():
    """A trace of three steps of a small jitted gradient step, recorded on a
    TPU v5e, with the compiled module's HLO text."""
    devices, hosts = tr.load(str(DATA / "tiny_v5e.xplane.pb"))
    hlo = (DATA / "tiny_v5e_hlo.txt").read_text()
    names = tr.op_names(hlo)
    assert list(devices) == ["/device:TPU:0"]

    def classify(name, stats):
        op = names.get(stats.get("hlo_op", name), "")
        return "grad" if ("jvp(" in op or "transpose(" in op) else "update"

    red = tr.reduce_device(devices["/device:TPU:0"], classify,
                           tr.collective_kinds(hlo))
    evs = devices["/device:TPU:0"]
    span = max(e[2] for e in evs) - min(e[1] for e in evs)
    assert 0 < red["busy_ns"] <= span
    assert red["class_ns"]["grad"] > 0 and red["class_ns"]["update"] > 0
    assert sum(red["class_ns"].values()) == pytest.approx(
        sum(red["ops"].values()))
    assert red["exposed_ns"] == 0.0
    assert hosts


@pytest.mark.parametrize("seed", [0, 2**31 + 11])
def test_traffic_is_a_function_of_the_seed(seed):
    cfg = config("qwen2.5-14b-1L")
    traffic = json.loads((BENCH / "traffic" / "muon-2x2048.json")
                         .read_text())
    a = ZipfTokens(traffic, cfg, seed).batch_at(5)
    b = ZipfTokens(traffic, cfg, seed).batch_at(5)
    c = ZipfTokens(traffic, cfg, seed + 1).batch_at(5)
    assert a["tokens"].shape == (2, 2048) and a["tokens"].dtype == np.int32
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    np.testing.assert_array_equal(a["tokens"][:, 1:], a["labels"][:, :-1])
    assert not np.array_equal(a["tokens"], c["tokens"])
    assert not np.array_equal(a["tokens"][0], a["tokens"][1])
    assert 0 <= a["tokens"].min() and a["tokens"].max() < cfg["vocab_size"]


def test_traffic_follows_its_zipf_law():
    """The commonest id takes 1/H_V of the tokens at exponent 1, whichever
    id the seed makes it."""
    cfg = config("qwen2.5-14b-1L")
    traffic = json.loads((BENCH / "traffic" / "muon-2x2048.json")
                         .read_text())
    V = cfg["vocab_size"]
    gen = ZipfTokens(traffic, cfg, 3)
    ids = np.concatenate([gen.batch_at(k)["tokens"].ravel()
                          for k in range(20)])
    counts = np.bincount(ids, minlength=V)
    top = np.sort(counts)[::-1]
    harmonic = np.sum(1.0 / np.arange(1, V + 1))
    assert top[0] / ids.size == pytest.approx(1 / harmonic, rel=0.05)
    assert top[1] / ids.size == pytest.approx(1 / (2 * harmonic), rel=0.08)
    assert np.argmax(counts) != np.argmax(np.bincount(
        np.concatenate([ZipfTokens(traffic, cfg, 4).batch_at(k)["tokens"]
                        .ravel() for k in range(20)]), minlength=V))
