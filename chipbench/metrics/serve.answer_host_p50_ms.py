"""Serving layer: median, over the window's lane batches (one value per
``batch_id``), of the seconds from the batch's rows being ready on the
device (``t_ready``) to its answers resolved (``t_done``): the host's
part of answering, the server's ``serve.answer`` span (row copies, path
walks, ticket resolution). Nothing is read where the program's
``RequestTrace`` has no ``t_ready``."""
from chipbench.stats import percentile


def read(ctx):
    spans = {}
    for r in ctx.requests:
        t = r.trace
        if getattr(t, "t_ready", None) is not None and t.t_done is not None:
            spans[t.batch_id] = t.t_done - t.t_ready
    p = percentile(list(spans.values()), 50)
    return None if p is None else 1e3 * p
