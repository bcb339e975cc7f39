"""Serving layer: mean bytes the server copied from the device to the
host per answered request (``RequestTrace.d2h_bytes``: a point-to-point
lane's whole ``dist`` and ``pred`` rows; results left on the device
count 0). Nothing is read where the program does not count them."""


def read(ctx):
    copied = [getattr(r.trace, "d2h_bytes", None) for r in ctx.requests
              if r.failed is None and r.trace is not None]
    copied = [b for b in copied if b is not None]
    return sum(copied) / len(copied) if copied else None
