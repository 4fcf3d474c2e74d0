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


def test_self_times_and_union():
    events = [ev("while", 0, 100), ev("a", 10, 40), ev("b", 50, 60),
              ev("c", 120, 130)]
    own = {e[0]: t for e, t in tr.self_times(events)}
    assert own == {"while": 60.0, "a": 30.0, "b": 10.0, "c": 10.0}
    assert tr.union([(0, 10), (5, 20), (30, 40)]) == [(0, 20), (30, 40)]


def test_reduce_device_classes_and_exposed_collectives():
    events = [ev("fusion.1", 0, 100), ev("all-to-all.2", 80, 150),
              ev("fusion.3", 200, 250), ev("all-gather-start.4", 240, 300)]
    red = tr.reduce_device(events, lambda n, s: "x" if "fusion" in n else
                           "y")
    assert red["busy_ns"] == 250.0          # [0, 150] and [200, 300]
    assert red["class_ns"] == {"x": 150.0, "y": 130.0}
    assert red["exposed_ns"] == 50.0 + 50.0  # [100, 150] and [250, 300]
    assert red["exposed_by"] == {"all-to-all": 50.0, "all-gather": 50.0}
    assert red["gaps"] == [(150.0, 200.0)]


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
    names = tr.op_names((DATA / "tiny_v5e_hlo.txt").read_text())
    assert list(devices) == ["/device:TPU:0"]

    def classify(name, stats):
        op = names.get(stats.get("hlo_op", name), "")
        return "grad" if ("jvp(" in op or "transpose(" in op) else "update"

    red = tr.reduce_device(devices["/device:TPU:0"], classify)
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
