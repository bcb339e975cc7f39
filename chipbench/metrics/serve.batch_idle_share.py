"""Serving layer: share of the traced window in which no operation runs
on the device while the serving thread is inside a ``serve.batch`` span
(forming the batch, dispatching, waiting, copying rows, walking paths):
the idle the serving loop causes, as against the idle in which it waits
for a request (``serve.wait_work``). Averaged over the chips used.

The profiler records a span only if it opens and closes while the trace
runs, so the window is cut to the part the serving thread's recorded
spans cover, from the first one's start to the last one's end. Nothing
is read from a trace without ``serve.batch`` spans."""
from chipbench import trace_reduce

BATCH = "serve.batch"
SERVING = (BATCH, "serve.wait_work")


def read(ctx):
    if ctx.trace is None or not ctx.trace.device:
        return None
    lo, hi = ctx.trace_window
    spans = trace_reduce.clip(
        [e for e in ctx.trace.host if e[0] in SERVING], lo, hi)
    batches = trace_reduce.merged(e for e in spans if e[0] == BATCH)
    if not batches:
        return None
    lo, hi = min(s for _, s, _ in spans), max(e for _, _, e in spans)
    idle = [sum(_overlap(g, batches)
                for g in trace_reduce.idle_gaps(ops, lo, hi))
            for ops in ctx.trace.device]
    return 100.0 * sum(idle) / len(idle) / (hi - lo)


def _overlap(gap, spans):
    a, b = gap
    return sum(max(0.0, min(b, e) - max(a, s)) for s, e in spans)
