"""Relaxation: the ``edge`` backend's sweeps as a share of the HBM
roofline, over the device time of the batched round-driver program.

Bytes the algorithm needs, from shapes and the result's telemetry: each
batched sweep reads the int32 ``src``/``dst``/``w`` arrays once
(12 bytes per edge), at least as many batched sweeps as the lane with
the most; each lane's sweep gathers its frontier flag (1 byte) and the
source's and destination's distances (4 + 4) and scatters one int32
candidate (4) per edge. Lane sweeps are light sweeps plus one heavy
sweep per bucket. The time is the whole driver program's, bucket scans
included, so the share is a lower bound of the sweeps' own."""
from chipbench import trace_reduce

DRIVER = "_run_many_vmapped"


def read(ctx):
    if ctx.trace is None or not ctx.trace.modules or ctx.peaks is None:
        return None
    lo, hi = ctx.trace_window
    runs = [e for e in ctx.trace.modules[0] if DRIVER in e[0]]
    total_bytes, secs = 0.0, 0.0
    for req, (_, s, e) in zip(ctx.requests, runs):
        if s < lo or e > hi or not req.lanes:
            continue
        sweeps = [b + i for b, i in req.lanes]
        total_bytes += ctx.m * (12.0 * max(sweeps) + 13.0 * sum(sweeps))
        secs += (e - s) / 1e9
    return trace_reduce.roofline_share(total_bytes, secs,
                                       ctx.peaks["hbm_bytes_per_s"])
