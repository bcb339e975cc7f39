"""Kernels: ``grid_relax``'s share of the HBM roofline. The algorithm
needs 9 bytes per cell and call (read the int32 tent and the int8 free
mask, write the int32 result); the kernel is memory bound (a few
integer operations per byte), so the bound is bytes over peak HBM
bandwidth, against the summed device time of its events.

A Pallas kernel's trace event is named by its HLO instruction
(``%body.39 = s32[896,896]{...} custom-call(s32[1,1]{...} ...``), not by
the kernel; ``grid_relax`` is the custom call that returns the int32
grid and takes the bucket index as a (1, 1) block."""
import re

from chipbench import trace_reduce

SIGNATURE = re.compile(r" = s32\[\d+,\d+\]\{[^}]*\} custom-call\(s32\[1,1\]")


def read(ctx):
    if ctx.trace is None or ctx.grid is None or ctx.peaks is None:
        return None
    lo, hi = ctx.trace_window
    n, secs = 0, 0.0
    for ops in ctx.trace.device:
        k, ns = trace_reduce.matching(trace_reduce.clip(ops, lo, hi),
                                      lambda s: bool(SIGNATURE.search(s)))
        n, secs = n + k, secs + ns / 1e9
    h, w = ctx.grid
    return trace_reduce.roofline_share(9.0 * h * w * n, secs,
                                       ctx.peaks["hbm_bytes_per_s"])
