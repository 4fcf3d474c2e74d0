"""Device self time per step, in ms, under the program's
``dmuon.orthogonalize`` scope: the owner-side orthogonalizer (Gram
Newton-Schulz for ``muon``).  On one chip XLA fuses the owner layout's
pack, momentum, unpack, scale, weight decay and learning rate into the
first and last NS fusions, so this time holds them too.  Mean over the
cell's chips; None where the step has no such scope."""

from bench.scopes import scopes_ms


def read(ctx):
    return scopes_ms(ctx, "dmuon.orthogonalize")
