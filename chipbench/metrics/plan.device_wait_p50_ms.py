"""Facade: median, over the window's lane batches (one value per
``batch_id``), of the seconds from the batch's dispatch returning
(``t_solve``) to its ``dist`` and ``pred`` being ready on the device
(``t_ready``, the end of the server's ``serve.await_device`` span): how
long the serving thread waits for the device after dispatch. Nothing is
read where the program's ``RequestTrace`` has no ``t_ready``."""
from chipbench.stats import percentile


def read(ctx):
    waits = {}
    for r in ctx.requests:
        t = r.trace
        if getattr(t, "t_ready", None) is not None and t.t_solve is not None:
            waits[t.batch_id] = t.t_ready - t.t_solve
    p = percentile(list(waits.values()), 50)
    return None if p is None else 1e3 * p
