"""Quickstart: the three-line DMuon API (paper Fig. 1a) on a tiny LM.

    PYTHONPATH=src python examples/quickstart.py [--variant muon|normuon|muonbp|adamw]

Builds a reduced smollm config, dedicates parameters, trains 20 steps with
owner-centric DMuon (or a registered optimizer variant) and prints the loss
curve.
"""

import argparse

import jax

from repro import configs
from repro.core import api                              # the drop-in module
from repro.core.muon import MuonConfig
from repro.data.pipeline import DataConfig, batch_for_step
from repro.models import model_fns
from repro.train.step import init_state, make_train_step
from repro.launch.compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--variant", default="muon",
                    choices=sorted(api.VARIANTS),
                    help="optimizer variant (see the registry in core/api.py)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--pipeline", default="fused",
                    choices=["fused", "bucketed"],
                    help="optimizer-step schedule (docs/DESIGN.md §6)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = configs.get("smollm-360m", reduced=True)
    shapes = jax.eval_shape(lambda k: model_fns(cfg).init(cfg, k),
                            jax.random.PRNGKey(0))

    # --- the paper's three lines -----------------------------------------
    plan = api.dedicate_params(shapes)                  # 1. dedicate
    opt = api.Muon(plan, config=MuonConfig(             # 2. construct
        learning_rate=0.02, momentum=0.95, variant=args.variant,
        pipeline=args.pipeline))
    state = init_state(cfg, opt, jax.random.PRNGKey(0))  # 3. init / update
    # ----------------------------------------------------------------------

    print(f"variant: {args.variant} — {opt.variant.description}")
    print(f"matrices under Muon: {plan.stats['num_matrices']} in "
          f"{plan.stats['num_groups']} groups; "
          f"{plan.stats['num_adamw_leaves']} AdamW leaves")

    step = make_train_step(cfg, opt, donate=False)
    dcfg = DataConfig(vocab=cfg.vocab, seq_len=64, global_batch=8)
    for i in range(args.steps):
        state = step(state, batch_for_step(dcfg, i))
        if i % 5 == 4:
            print(f"step {int(state.step):3d}  loss_ema "
                  f"{float(state.loss_ema):.4f}")
    print("done.")


if __name__ == "__main__":
    main()
