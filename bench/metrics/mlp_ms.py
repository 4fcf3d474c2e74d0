"""Device self time per step, in ms, under the program's ``model.mlp``
scope: gate, up and down projections and the activation, in the forward,
the backward and the remat recompute.  Mean over the cell's chips; None
where the step has no such scope."""

from bench.scopes import scopes_ms


def read(ctx):
    return scopes_ms(ctx, "model.mlp")
