"""Device time per step, in ms, of the step program's ops whose HLO
``op_name`` lies under ``jvp(`` or ``transpose(``: forward, backward and the
remat recompute.  Mean over the cell's chips."""


def read(ctx):
    return class_ms(ctx, "fwd_bwd")


def class_ms(ctx, cls):
    red = ctx.get("trace")
    if not red:
        return None
    ns = [r["class_ns"].get(cls, 0.0) for r in red.values()]
    if not any(ns):
        return None
    return sum(ns) / len(ns) / ctx["steps"] * 1e-6
