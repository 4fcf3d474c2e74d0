"""Device time per step, in ms, in which an all-to-all ran with no other op
beside it on its chip: the owner exchange (gradients to their owners and
updates back, ``dmuon.stage_in`` and ``dmuon.publish``) where no compute
hides it.  ``exposed_by["all-to-all"]`` of each chip's reduced trace
(``bench/trace.py``), mean over the cell's chips; None where no chip ran an
all-to-all (one chip)."""

from bench.metrics.collective_exposed_ms import exposed_ms


def read(ctx):
    return exposed_ms(ctx, "all-to-all")
