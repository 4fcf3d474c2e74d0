"""Device time per step, in ms, in which a collective of any kind ran with
no other op beside it on its chip: the owner exchange's all-to-alls and the
tensor-parallel all-gathers, reduce-scatters and all-reduces where no
compute hides them.  ``exposed_ns`` of each chip's reduced trace
(``bench/trace.py``), mean over the cell's chips; None where no chip ran a
collective (one chip)."""


def exposed_ms(ctx, kind=None):
    """Exposed collective time per step in ms, mean over chips: of ``kind``
    (a key of ``exposed_by``), or of every kind where None."""
    red = ctx.get("trace")
    if not red or not any(r["exposed_by"] if kind is None
                          else kind in r["exposed_by"]
                          for r in red.values()):
        return None
    ns = [r["exposed_ns"] if kind is None else r["exposed_by"].get(kind, 0.0)
          for r in red.values()]
    return sum(ns) / len(ns) / ctx["steps"] * 1e-6


def read(ctx):
    return exposed_ms(ctx)
