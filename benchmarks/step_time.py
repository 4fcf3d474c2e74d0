"""Table 1 / Figure 8 analogue: optimizer-step and end-to-end step time for
DMuon vs gather-then-compute Muon (Muon-AG) vs AdamW.

Three parts:
  (a) measured — wall-clock of the optimizer modes + full train step on this
      host (single CPU device, reduced workload, identical semantics), for
      both optimizer-step pipelines ('fused' one-phase vs 'bucketed'
      stage_in/compute/publish; docs/DESIGN.md §6) at accum_steps 1 and 4
      (the accumulation-overlapped schedule only exists at accum > 1);
  (b) derived  — the owner-vs-adamw overhead gap per pipeline (the paper's
      near-Adam headline, and the number the bucketed pipeline is meant to
      shrink on multi-bucket configs);
  (c) derived  — per-rank optimizer time at 8..256 ranks from the measured
      per-(shape,batch) cost model, exactly the quantity Table 1 reports.

The bench config is multi-bucket by construction (GQA kv projections give a
second Gram dimension), so the bucketed schedule has something to pipeline.
"""

from __future__ import annotations

import jax

from benchmarks.common import record, record_to_csv
from repro import configs
from repro.core import api, load_balance
from repro.core.muon import MuonConfig
from repro.data.pipeline import DataConfig, batch_for_step
from repro.models import model_fns
from repro.train.step import init_state, make_train_step

CONFIG_TAG = "smollm-360m-reduced"
ACCUMS = (1, 4)


def _setup(mode: str, variant: str = "muon", pipeline: str = "fused",
           accum_steps: int = 1):
    cfg = configs.get("smollm-360m", reduced=True, n_layers=8, d_model=256,
                      n_heads=8, n_kv_heads=4, d_ff=704, vocab=2048,
                      remat=False)
    shapes = jax.eval_shape(lambda k: model_fns(cfg).init(cfg, k),
                            jax.random.PRNGKey(0))
    plan = api.dedicate_params(shapes, num_owners=1, strategy="greedy")
    opt = api.Muon(plan, config=MuonConfig(mode=mode, variant=variant,
                                           pipeline=pipeline))
    state = init_state(cfg, opt, jax.random.PRNGKey(0))
    step = make_train_step(cfg, opt, donate=False, accum_steps=accum_steps)
    dcfg = DataConfig(vocab=cfg.vocab, seq_len=128, global_batch=8)
    batch = batch_for_step(dcfg, 0)
    return cfg, plan, opt, state, step, batch


def _measure_paired(cases, accum_steps: int, repeats: int) -> list[dict]:
    """Interleaved (paired) sampling across all cases of one accum level.

    The modes/pipelines being compared differ by tens of ms while the host
    drifts by more than that between block measurements — so sample them
    round-robin: one timed call of each case per round.  Slow drift then
    hits every case equally and the *relative* numbers (the quantity every
    derived row reports) stay meaningful.
    """
    import time

    built = []
    for mode, variant, pipe in cases:
        cfg, plan, opt, state, step, batch = _setup(mode, variant, pipe,
                                                    accum_steps)
        opt_fn = opt_args = None
        if accum_steps == 1:
            from repro.train.step import make_loss_fn
            grads = jax.jit(jax.grad(make_loss_fn(cfg)))(state.params, batch)
            opt_fn = jax.jit(lambda g, s, p, _o=opt: _o.update(g, s, p))
            opt_args = (grads, state.opt_state, state.params)
        built.append({"tag": (mode, variant, pipe), "step": step,
                      "args": (state, batch), "opt_fn": opt_fn,
                      "opt_args": opt_args, "t_step": [], "t_opt": []})
    for b in built:                                    # warmup (compile)
        jax.block_until_ready(b["step"](*b["args"]))
        jax.block_until_ready(b["step"](*b["args"]))
        if b["opt_fn"] is not None:
            jax.block_until_ready(b["opt_fn"](*b["opt_args"]))
    for _ in range(repeats):
        for b in built:
            t0 = time.perf_counter()
            jax.block_until_ready(b["step"](*b["args"]))
            b["t_step"].append(time.perf_counter() - t0)
            if b["opt_fn"] is not None:
                t0 = time.perf_counter()
                jax.block_until_ready(b["opt_fn"](*b["opt_args"]))
                b["t_opt"].append(time.perf_counter() - t0)
    recs = []
    for b in built:
        mode, variant, pipe = b["tag"]
        recs.append(record(f"step_time/end_to_end/accum{accum_steps}",
                           config=CONFIG_TAG, variant=variant, mode=mode,
                           pipeline=pipe, samples_s=b["t_step"]))
        if b["t_opt"]:
            recs.append(record("step_time/optimizer", config=CONFIG_TAG,
                               variant=variant, mode=mode, pipeline=pipe,
                               samples_s=b["t_opt"]))
    return recs


def _derived_pipeline_records(ranks: int = 16,
                              tokens_per_step: float = 2 ** 21) -> list[dict]:
    """Mesh-scale roofline model of the two optimizer schedules (derived —
    single-host wall clock cannot show comm/compute overlap; this is the
    same cost-model convention as the table1 rows).

    Per Gram bucket b on the qwen2.5-14b census at ``ranks`` owners:
      compute(b)  = bottleneck rank's Gram-NS time (measured-form cost model)
      comm(b)     = bottleneck rank's staged all-to-all time, bf16 payload
    fused     = Σ_b (comm_in + compute + comm_out)   (serialized phases)
    bucketed  = Σ_b max(compute(b), comm_out(b-1)) + comm_out(b_last):
                with accum prestaging every stage_in rides under the next
                microbatch's fwd/bwd (orders of magnitude longer), and each
                publish overlaps the next bucket's compute (docs/DESIGN.md
                §6) — only the final publish is exposed.
    The near-Adam headline = optimizer delta over a 6·P·tokens/chip roofline
    step time.
    """
    import numpy as np

    from repro.launch.mesh import HBM_BW, ICI_BW, PEAK_FLOPS_BF16

    census = {}
    full_cfg = configs.get("qwen2.5-14b")
    shapes = jax.eval_shape(lambda k: model_fns(full_cfg).init(full_cfg, k),
                            jax.random.PRNGKey(0))
    plan = api.dedicate_params(shapes, num_owners=1, strategy="round_robin")
    for g in plan.groups.values():
        census[g.key] = census.get(g.key, 0) + g.count
    cm = load_balance.analytic_cost_model(census)
    asn = load_balance.solve_greedy(census, cm, ranks)
    counts = asn.counts()

    buckets: dict = {}
    for (m, n) in census:
        buckets.setdefault(m, []).append((m, n))
    sched = sorted(buckets.items(), key=lambda kv: -kv[0])

    compute_b, comm_b = [], []
    for _, shs in sched:
        loads = np.zeros(ranks)
        byts = np.zeros(ranks)
        for s in shs:
            for b, r in asn.chunks[s]:
                loads[r] += cm.cost(s, b)
            byts += counts[s] * s[0] * s[1] * 2          # bf16 payload
        compute_b.append(float(loads.max()))
        comm_b.append(float(byts.max()) * (ranks - 1) / ranks / ICI_BW)

    nb = len(sched)
    fused = sum(2 * c + t for c, t in zip(comm_b, compute_b))
    bucketed = sum(max(compute_b[i], comm_b[i - 1] if i > 0 else 0.0)
                   for i in range(nb)) + comm_b[-1]

    n_params = sum(m * n * c for (m, n), c in census.items())
    adamw = n_params / ranks * 28 / HBM_BW               # m,v,p,g @ fp32
    step_fb = 6 * n_params * tokens_per_step / (PEAK_FLOPS_BF16 * ranks)

    recs = [record(f"step_time/derived_mesh{ranks}/optimizer",
                   config="qwen2.5-14b", mode="adamw", value=adamw * 1e6,
                   derived="model_us")]
    for pipe, t in (("fused", fused), ("bucketed", bucketed)):
        recs.append(record(f"step_time/derived_mesh{ranks}/optimizer",
                           config="qwen2.5-14b", mode="owner", pipeline=pipe,
                           value=t * 1e6, derived="model_us"))
        recs.append(record(
            f"step_time/derived_mesh{ranks}/overhead_vs_adamw_pct",
            config="qwen2.5-14b", mode="owner", pipeline=pipe,
            value=(t - adamw) / (step_fb + adamw) * 100.0, unit="pct",
            derived="model_pct"))
    return recs


def run_records(variant: str = "muon", pipeline: str = "both",
                repeats: int = 15) -> list[dict]:
    pipelines = ("fused", "bucketed") if pipeline == "both" else (pipeline,)
    records: list[dict] = []
    for accum in ACCUMS:
        # the owner rows carry the requested variant and both pipelines;
        # the gather/adamw baselines only have the one-phase program
        cases = [("owner", variant, pipe) for pipe in pipelines]
        cases += [("gather", "muon", "fused"), ("adamw", "muon", "fused")]
        records.extend(_measure_paired(cases, accum, repeats))

    def med(name, mode, pipe, accum):
        for r in records:
            if (r["name"] == f"step_time/{name}/accum{accum}"
                    and r["mode"] == mode and r["pipeline"] == pipe):
                return r["median_us"]
        return None

    # the acceptance metric: how close each owner pipeline gets to the adamw
    # step time (pct overhead; the bucketed schedule should sit closer)
    for accum in ACCUMS:
        adamw = med("end_to_end", "adamw", "fused", accum)
        for pipe in pipelines:
            owner = med("end_to_end", "owner", pipe, accum)
            if owner is None or adamw is None:
                continue
            records.append(record(
                f"step_time/overhead_vs_adamw_pct/accum{accum}",
                config=CONFIG_TAG, variant=variant, mode="owner",
                pipeline=pipe, value=(owner - adamw) / adamw * 100.0,
                unit="pct", derived="pct"))

    records.extend(_derived_pipeline_records(ranks=16))

    # -------- derived scaling table (Table 1 / Fig 8 shape) --------------
    census = {}
    full_cfg = configs.get("qwen2.5-14b")
    shapes = jax.eval_shape(lambda k: model_fns(full_cfg).init(full_cfg, k),
                            jax.random.PRNGKey(0))
    plan = api.dedicate_params(shapes, num_owners=1, strategy="round_robin")
    for g in plan.groups.values():      # aggregate per-leaf groups by shape
        census[g.key] = census.get(g.key, 0) + g.count
    cm = load_balance.analytic_cost_model(census)
    total_once = sum(cm.per_matrix(s) * n for s, n in census.items())
    for ranks in (8, 16, 32, 64, 128, 256):
        asn = load_balance.solve_greedy(census, cm, ranks)
        dmuon_t = asn.makespan(cm)
        records.append(record(
            f"table1/qwen2.5-14b/{ranks}ranks/dmuon_opt_ms",
            config="qwen2.5-14b", mode="owner", value=dmuon_t * 1e6,
            unit="model_us", derived=f"speedup={total_once/dmuon_t:.1f}x"))
    return records


def run(variant: str = "muon", pipeline: str = "both") -> list[str]:
    return [record_to_csv(r) for r in run_records(variant, pipeline)]


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--variant", default="muon",
                    help="optimizer variant for the owner-mode rows "
                         "(muon/normuon/muonbp/adamw; registry in core/api.py)")
    ap.add_argument("--pipeline", default="both",
                    choices=["fused", "bucketed", "both"],
                    help="optimizer-step schedule for the owner-mode rows")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    for r in run(variant=args.variant, pipeline=args.pipeline):
        print(r)
