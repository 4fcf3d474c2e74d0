"""Host time per step, in ms, that the loop (runtime/resilient.py) spends
outside the step call: the window's wall time over its steps, less the mean
of ``LoopReport.step_times`` (the step call through ``block_until_ready``)."""

import statistics


def read(ctx):
    if not ctx["step_times"]:
        return None
    return (ctx["window_s"] / ctx["steps"]
            - statistics.mean(ctx["step_times"])) * 1e3
