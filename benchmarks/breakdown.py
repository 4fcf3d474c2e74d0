"""Table 2 analogue: per-component breakdown of DMuon's optimizer-step
speedup, by disabling each component in isolation:

  symmetric Gram kernel — Gram-space symmetric products vs full-GEMM Gram
                          (FLOP-exact model + measured Gram-vs-standard time)
  owner + load balance  — one owner per matrix (makespan) vs replicated NS
  batching + autotune   — batched stacks vs per-matrix launches (measured)

plus (``--pipeline``) a stage-level breakdown of the bucketed optimizer
schedule (docs/DESIGN.md §6): stage_in (pack + owner all-to-all), compute
(momentum + NS on the local slice), publish (reshard back + scale/wd/lr) —
the three phases the pipeline overlaps, timed in isolation.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import record, record_to_csv, time_samples
from repro.core import load_balance
from repro.core.gram_ns import GramNSConfig, gram_newton_schulz, gram_ns_flops
from repro.core.newton_schulz import newton_schulz

CENSUS = {(256, 1024): 32, (256, 256): 64, (128, 512): 96}
RANKS = 16


def _variant_records(variants) -> list[dict]:
    """Orthogonalizer-phase cost of registered variants on one owner stack:
    the refresh step (full NS) vs the steady-state step (MuonBP's cached
    reuse, Dion2's warm-basis path; identical to refresh for stateless
    variants).  ``muon`` is always measured first as the baseline, and every
    other variant's refresh row carries a ``vs_muon=`` ratio quantifying the
    ortho-phase cost each backend saves (or pays) over the plain Gram path."""
    from repro.core import api
    from repro.core.muon import MuonConfig
    from repro.core.orthogonalize import make_orthogonalizer
    from repro.core.owner_comms import OwnerLayout, group_key_str

    ordered = ["muon"] + [v for v in dict.fromkeys(variants) if v != "muon"]
    x = jax.random.normal(jax.random.PRNGKey(2), (16, 128, 512)) * 0.02
    plan = api.dedicate_params({"w": x}, num_owners=1, strategy="greedy")
    stacks = {group_key_str("w"): x}

    recs: list[dict] = []
    muon_refresh_s = None
    for variant in ordered:
        spec = api.get_variant(variant)
        if spec.elementwise:
            continue
        mcfg = MuonConfig(variant=variant)
        layout = OwnerLayout(plan)
        ortho = make_orthogonalizer(spec.orthogonalizer, mcfg)
        state = ortho.init_state(layout, mcfg)

        fn = jax.jit(lambda sts, step, st, o=ortho, lo=layout, c=mcfg: o(
            sts, step=step, state=st, layout=lo, cfg=c))
        t_refresh = time_samples(fn, stacks, jnp.zeros((), jnp.int32), state)
        derived = ""
        if variant == "muon":
            muon_refresh_s = min(t_refresh)
        elif muon_refresh_s is not None:
            derived = f"vs_muon={min(t_refresh) / muon_refresh_s:.2f}x"
        recs.append(record("table2/variant/ortho_refresh", variant=variant,
                           samples_s=t_refresh, derived=derived))
        # steady state: advance past the refresh boundary (step % period
        # != 0 for MuonBP; a warm — nonzero — basis for Dion2)
        _, state1 = fn(stacks, jnp.zeros((), jnp.int32), state)
        t_steady = time_samples(fn, stacks, jnp.ones((), jnp.int32), state1)
        recs.append(record(
            "table2/variant/ortho_steady", variant=variant,
            samples_s=t_steady,
            derived=f"refresh/steady="
                    f"{min(t_refresh)/min(t_steady):.2f}x"))
    return recs


def _pipeline_records(variant: str, pipeline: str) -> list[dict]:
    """Stage-level cost of the bucketed schedule on a multi-bucket toy
    census: stage_in vs compute vs publish vs the whole pipelined step."""
    import numpy as np

    from repro.core import api
    from repro.core.muon import MuonConfig
    from repro.core.pipeline import BucketPipeline

    params = {f"w{i}": np.zeros((8, m, n), np.float32)
              for i, (m, n) in enumerate(sorted(CENSUS))}
    rng = jax.random.PRNGKey(3)
    grads = {p: jax.random.normal(jax.random.fold_in(rng, i),
                                  v.shape) * 0.02
             for i, (p, v) in enumerate(params.items())}
    plan = api.dedicate_params(params, num_owners=1, strategy="greedy")
    cfg = MuonConfig(variant=variant, pipeline=pipeline)
    spec = api.get_variant(cfg.variant)
    if spec.elementwise:
        return []
    pipe = BucketPipeline(plan, cfg, spec=spec)
    opt = api.Muon(plan, config=cfg)
    state = opt.init(params)
    recs = []

    stage = jax.jit(lambda g: pipe.stage_in_all(g))
    recs.append(record("table2/pipeline/stage_in", variant=variant,
                       pipeline=pipeline, samples_s=time_samples(stage,
                                                                 grads)))
    staged = stage(grads)
    comp = jax.jit(lambda st, s: pipe.run_staged(st, params, s)[:2])
    recs.append(record("table2/pipeline/compute_publish", variant=variant,
                       pipeline=pipeline,
                       samples_s=time_samples(comp, staged, state)))
    full = jax.jit(lambda g, s: opt.update(g, s, params))
    recs.append(record("table2/pipeline/full_step", variant=variant,
                       pipeline=pipeline,
                       samples_s=time_samples(full, grads, state)))
    return recs


DEFAULT_VARIANTS = ("muon", "dion2", "adamuon")


def run_records(variants=DEFAULT_VARIANTS,
                pipeline: str = "bucketed") -> list[dict]:
    recs: list[dict] = []
    cfg = GramNSConfig(num_steps=5)

    # ---- symmetric-kernel share (FLOP-exact; kernels halve every product)
    full = sym = 0.0
    for (m, n), c in CENSUS.items():
        f = gram_ns_flops(m, n, 5, batch=c)
        full += f["gram_full_gemm"]
        sym += f["gram_symmetric_kernel"]
    recs.append(record("table2/symmetric_kernel_flop_saving_pct",
                       value=(1 - sym / full) * 100, unit="pct",
                       derived="pct"))

    # ---- owner + LB: replicated cost vs balanced makespan
    cm = load_balance.analytic_cost_model(CENSUS)
    asn = load_balance.solve_greedy(CENSUS, cm, RANKS)
    replicated = sum(cm.per_matrix(s) * n for s, n in CENSUS.items())
    recs.append(record("table2/owner_lb_speedup",
                       value=replicated / asn.makespan(cm) * 100,
                       unit="ratio_x100", derived="ratio_x100"))
    r0 = load_balance.rank0(CENSUS, RANKS)
    recs.append(record("table2/rank0_ablation_slowdown",
                       value=r0.makespan(cm) / asn.makespan(cm) * 100,
                       unit="ratio_x100", derived="ratio_x100"))

    # ---- batching: measured batched stack vs per-matrix loop
    m, n, b = 128, 512, 16
    x = jax.random.normal(jax.random.PRNGKey(0), (b, m, n))
    fn_b = jax.jit(lambda v: gram_newton_schulz(v, cfg,
                                                assume_short_fat=True))
    t_batched = min(time_samples(fn_b, x))
    fn_1 = jax.jit(lambda v: gram_newton_schulz(v, cfg,
                                                assume_short_fat=True))
    t_single = min(time_samples(fn_1, x[:1]))
    recs.append(record("table2/batching_speedup",
                       value=(t_single * b) / t_batched * 100,
                       unit="ratio_x100", derived="ratio_x100"))

    # ---- gram vs standard NS (measured, fat matrices where gram wins)
    xf = jax.random.normal(jax.random.PRNGKey(1), (8, 256, 2048))
    t_gram = min(time_samples(jax.jit(
        lambda v: gram_newton_schulz(v, cfg, assume_short_fat=True)), xf))
    t_std = min(time_samples(jax.jit(
        lambda v: newton_schulz(v, num_steps=5)), xf))
    recs.append(record("table2/gram_vs_standard_ns_speedup",
                       value=t_std / t_gram * 100, unit="ratio_x100",
                       derived="ratio_x100"))

    # ---- composed share attribution (normalized like Table 2)
    s_kernel = 1 - sym / full
    s_owner = 1 - 1 / (replicated / asn.makespan(cm))
    s_batch = 1 - t_batched / (t_single * b)
    tot = s_kernel + s_owner + s_batch
    for name, s in (("symmetric_kernel", s_kernel),
                    ("owner_scheduling_lb", s_owner),
                    ("autotune_batching", s_batch)):
        recs.append(record(f"table2/share/{name}", value=s / tot * 100,
                           unit="pct", derived="share_pct"))

    # ---- pluggable-variant orthogonalizer overhead + pipeline stages
    variants = tuple(variants)
    recs.extend(_variant_records(variants))
    for v in dict.fromkeys(variants):
        recs.extend(_pipeline_records(v, pipeline))
    return recs


def run(variants=DEFAULT_VARIANTS, pipeline: str = "bucketed") -> list[str]:
    return [record_to_csv(r) for r in run_records(variants, pipeline)]


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--variant", action="append", default=None,
                    help="variant for the orthogonalizer-overhead rows; "
                         "repeatable (muon is always measured as baseline); "
                         "default: %s" % (DEFAULT_VARIANTS,))
    ap.add_argument("--pipeline", default="bucketed",
                    choices=["fused", "bucketed"],
                    help="schedule for the pipeline-stage rows")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    for r in run(variants=tuple(args.variant or DEFAULT_VARIANTS),
                 pipeline=args.pipeline):
        print(r)
