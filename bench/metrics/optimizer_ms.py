"""Device time per step, in ms, of every other op of the step program (the
Muon or AdamW update, and whatever else lies outside the gradient).  Mean
over the cell's chips."""

from bench.metrics.fwd_bwd_ms import class_ms


def read(ctx):
    return class_ms(ctx, "optimizer")
