"""Facade: median seconds from a lane batch's dispatch returning
(``t_solve``) to its answers resolved on the host (``t_done``). The
dispatch is asynchronous, so this holds the device solve the rows wait
for, the two whole-row copies per lane and the path walk."""
from chipbench.stats import percentile


def read(ctx):
    spans = [r.trace.t_done - r.trace.t_solve for r in ctx.requests
             if r.trace is not None and r.trace.t_solve is not None
             and r.trace.t_done is not None and r.failed is None]
    p = percentile(spans, 50)
    return None if p is None else 1e3 * p
