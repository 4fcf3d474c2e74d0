"""Device self time per step, in ms, under the program's ``model.attention``
scope: projections, rope, scores, softmax and values in the forward, the
backward and the remat recompute, and the causal mask the layer scan hoists
out of them.  Mean over the cell's chips; None where the step has no such
scope."""

from bench.scopes import scopes_ms


def read(ctx):
    return scopes_ms(ctx, "model.attention")
