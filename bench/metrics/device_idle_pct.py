"""Share of the traced window, in percent, in which no op ran on the device:
one less the union of device op intervals over the window's wall time.
Mean over the cell's chips."""


def read(ctx):
    red = ctx.get("trace")
    if not red:
        return None
    busy = sum(r["busy_ns"] for r in red.values()) / len(red) * 1e-9
    if busy <= 0:
        return None
    return 100.0 * (1.0 - busy / ctx["window_s"])
