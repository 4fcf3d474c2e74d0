"""The optimizer's share of its roofline, in percent: the least time its
work could take on the chip over ``optimizer_ms``.  The least time is the
larger of two bounds (``bench/flops.optimizer_work``): Gram Newton-Schulz
FLOP at the symmetric-product count over the bf16 peak, and the parameter,
gradient and optimizer-state bytes read and written once over HBM
bandwidth."""

from bench.flops import optimizer_work
from bench.metrics.fwd_bwd_ms import class_ms


def bound(ctx):
    """``(least seconds, which bound)`` per step and chip."""
    p = ctx["peaks"]
    w = optimizer_work(ctx["config"], ctx["mode"], chips=ctx["chips"])
    t_flop = w["flops"] / p["bf16_flops"]
    t_byte = w["bytes"] / p["hbm_bytes_per_s"]
    return (t_flop, "flops") if t_flop >= t_byte else (t_byte, "bytes")


def read(ctx):
    ms = class_ms(ctx, "optimizer")
    if not ms:
        return None
    return 100.0 * bound(ctx)[0] / (ms * 1e-3)
