"""Figure 1(c) analogue: wall-clock convergence of DMuon vs AdamW.

    PYTHONPATH=src python examples/dmuon_vs_adamw.py --steps 120

Trains the same ~5M model with both optimizers on the same synthetic stream
and prints aligned loss curves — Muon's per-step convergence advantage with
DMuon's near-AdamW step cost is the paper's wall-clock argument.
"""

import argparse
import time

import jax

from repro import configs
from repro.core import api
from repro.core.muon import MuonConfig
from repro.data.pipeline import DataConfig, batch_for_step
from repro.models import model_fns
from repro.train.step import init_state, make_loss_fn, make_train_step
from repro.launch.compile_cache import enable_compile_cache


def train(cfg, mode, steps, lr):
    shapes = jax.eval_shape(lambda k: model_fns(cfg).init(cfg, k),
                            jax.random.PRNGKey(0))
    plan = api.dedicate_params(shapes, strategy="greedy")
    opt = api.Muon(plan, config=MuonConfig(mode=mode, learning_rate=lr,
                                           adam_lr=3e-3))
    state = init_state(cfg, opt, jax.random.PRNGKey(0))
    step = make_train_step(cfg, opt, donate=False)
    loss_fn = jax.jit(make_loss_fn(cfg))
    dcfg = DataConfig(vocab=cfg.vocab, seq_len=128, global_batch=8)
    curve, times = [], []
    t0 = time.time()
    for i in range(steps):
        batch = batch_for_step(dcfg, i)
        if i % 10 == 0:
            curve.append(float(loss_fn(state.params, batch)))
            times.append(time.time() - t0)
        state = step(state, batch)
    return curve, times


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=120)
    args = ap.parse_args()
    enable_compile_cache()
    cfg = configs.get("smollm-360m", n_layers=4, d_model=256, n_heads=4,
                      n_kv_heads=2, d_ff=704, vocab=4096, head_dim=64,
                      remat=False)
    dm_curve, dm_t = train(cfg, "owner", args.steps, lr=0.02)
    ad_curve, ad_t = train(cfg, "adamw", args.steps, lr=0.02)
    print(f"{'step':>5} | {'DMuon loss':>10} | {'AdamW loss':>10}")
    for i, (a, b) in enumerate(zip(dm_curve, ad_curve)):
        print(f"{i*10:5d} | {a:10.4f} | {b:10.4f}")
    print(f"\nwall: DMuon {dm_t[-1]:.1f}s vs AdamW {ad_t[-1]:.1f}s "
          f"for {args.steps} steps")
    better = sum(1 for a, b in zip(dm_curve[2:], ad_curve[2:]) if a < b)
    print(f"DMuon ahead at {better}/{len(dm_curve)-2} checkpoints")


if __name__ == "__main__":
    main()
