"""End-to-end training driver (deliverable b): train a ~100M-class smollm
variant for a few hundred steps with DMuon, with checkpointing + restart.

    PYTHONPATH=src python examples/train_smollm.py --steps 200
    PYTHONPATH=src python examples/train_smollm.py --steps 200 --opt adamw
    PYTHONPATH=src python examples/train_smollm.py --resume   # from last ckpt

On this CPU container the default is a ~20M-param scaled config (wall-clock
budget); pass --full-360m to train the real smollm-360m architecture.
"""

import argparse
import time

import jax

from repro import configs
from repro.checkpoint.manager import CheckpointManager
from repro.core import api
from repro.core.muon import MuonConfig
from repro.data.pipeline import DataConfig, Pipeline, batch_for_step
from repro.models import model_fns
from repro.train.step import init_state, make_train_step
from repro.train.train_state import TrainState
from repro.launch.compile_cache import enable_compile_cache


def build(args):
    if args.full_360m:
        cfg = configs.get("smollm-360m")
    else:  # ~20M params: same family, CPU-budget width
        cfg = configs.get("smollm-360m", n_layers=8, d_model=384,
                          n_heads=6, n_kv_heads=2, d_ff=1024, vocab=8192,
                          head_dim=64, remat=False)
    shapes = jax.eval_shape(lambda k: model_fns(cfg).init(cfg, k),
                            jax.random.PRNGKey(0))
    plan = api.dedicate_params(shapes, strategy="greedy")
    opt = api.Muon(plan, config=MuonConfig(
        mode=args.opt if args.opt != "muon_ag" else "gather",
        learning_rate=args.lr, adam_lr=3e-3))
    return cfg, plan, opt


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--opt", default="owner",
                    choices=["owner", "muon_ag", "gather", "adamw"])
    ap.add_argument("--lr", type=float, default=0.02)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt-dir", default="/tmp/dmuon_smollm_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--full-360m", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    cfg, plan, opt = build(args)
    n_params = cfg.param_count()
    print(f"arch={cfg.name} params={n_params/1e6:.1f}M opt={args.opt} "
          f"muon_matrices={plan.stats['num_matrices']}")

    state = init_state(cfg, opt, jax.random.PRNGKey(0))
    mgr = CheckpointManager(args.ckpt_dir, keep=2)
    start = 0
    if args.resume and mgr.latest_step() is not None:
        restored = mgr.restore(like=state._asdict())
        state = TrainState(**restored)
        start = int(state.step)
        print(f"resumed from step {start}")

    step = make_train_step(cfg, opt, donate=False)
    dcfg = DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                      global_batch=args.batch)
    pipe = Pipeline(dcfg, start_step=start, prefetch=2)

    t0 = time.time()
    try:
        for i in range(start, args.steps):
            state = step(state, next(pipe))
            if (i + 1) % 10 == 0:
                rate = (i + 1 - start) / (time.time() - t0)
                print(f"step {i+1:4d}  loss_ema {float(state.loss_ema):.4f} "
                      f"  {rate:.2f} steps/s", flush=True)
            if (i + 1) % args.ckpt_every == 0:
                mgr.save(i + 1, state._asdict())
    finally:
        pipe.close()
        mgr.wait()
    print(f"final loss_ema {float(state.loss_ema):.4f}")


if __name__ == "__main__":
    main()
