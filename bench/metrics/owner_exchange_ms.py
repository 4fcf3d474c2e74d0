"""Device self time per step, in ms, under the program's ``dmuon.stage_in``
and ``dmuon.publish`` scopes: the owner layout's way in (compression, pack,
momentum, the all-to-all to owners on a mesh) and out (unpack, the way back,
scale, weight decay and learning rate).  Mean over the cell's chips; None
where the step has neither scope.  Only a mesh gives it something to read:
on one chip XLA fuses that work into the backward's and Newton-Schulz's
fusions, which carry their own scopes, and it reads 0."""

from bench.scopes import scopes_ms


def read(ctx):
    return scopes_ms(ctx, "dmuon.stage_in", "dmuon.publish")
