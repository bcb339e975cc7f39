"""Serving layer: 95th percentile of the seconds from when a request
was due to when the server put it into a lane batch (``t_batch``, the
server's ``time.monotonic`` clock, the one the generator uses)."""
from chipbench.stats import percentile


def read(ctx):
    waits = [r.trace.t_batch - r.due for r in ctx.requests
             if r.due is not None and r.trace is not None
             and r.trace.t_batch is not None]
    p = percentile(waits, 95)
    return None if p is None else 1e3 * p
