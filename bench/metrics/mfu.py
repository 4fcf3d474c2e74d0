"""Model FLOP utilization of the whole step, in percent: model FLOP per step
(``bench/flops.model_flops_per_step``, no recomputation) times steps per
second of the traced window, over chips times the chip's bf16 peak."""

from bench.flops import model_flops_per_step


def read(ctx):
    t = ctx["traffic"]
    f = model_flops_per_step(ctx["config"], int(t["batch"]),
                             int(t["seq_len"]))
    rate = ctx["steps"] / ctx["window_s"]
    return 100.0 * f * rate / (ctx["chips"] * ctx["peaks"]["bf16_flops"])
