"""Serving layer: mean share of a lane batch's lanes that carry a real
request, over the window's lane batches (the rest repeat the last
source)."""


def read(ctx):
    batches = {}
    for r in ctx.requests:
        t = r.trace
        if t is not None and t.t_batch is not None and t.batch_occupancy:
            batches[t.t_batch] = t.batch_occupancy
    if not batches:
        return None
    return 100.0 * sum(batches.values()) / len(batches)
