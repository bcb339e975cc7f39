"""Device: share of the traced window in which no operation ran on the
device, averaged over the chips used. Reads ``device.idle_share.<cell
kind>`` for every kind of cell."""
from chipbench import trace_reduce


def read(ctx):
    if ctx.trace is None or not ctx.trace.device:
        return None
    lo, hi = ctx.trace_window
    busy = [trace_reduce.busy_ns(ops, lo, hi) for ops in ctx.trace.device]
    return 100.0 * (1.0 - sum(busy) / len(busy) / (hi - lo))
