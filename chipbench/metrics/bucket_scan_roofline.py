"""Kernels: ``bucket_scan``'s share of the HBM roofline. The algorithm
needs 9 bytes per vertex and call (read the int32 tent and explored
words, write the int8 frontier mask); memory bound, so the bound is
bytes over peak HBM bandwidth, against the summed device time of its
events.

The kernel's trace event is named by its HLO instruction, not by the
kernel: ``bucket_scan`` is the custom call that returns the int8
frontier in 128-lane rows and the two int32 scalars, and takes the
bucket index from SMEM as an int32[1]."""
import re

from chipbench import trace_reduce

SIGNATURE = re.compile(
    r" = \(s8\[\d+,128\]\{[^}]*\}, s32\[2\]\{[^}]*\}\) custom-call\(s32\[1\]")


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    lo, hi = ctx.trace_window
    n, secs = 0, 0.0
    for ops in ctx.trace.device:
        k, ns = trace_reduce.matching(trace_reduce.clip(ops, lo, hi),
                                      lambda s: bool(SIGNATURE.search(s)))
        n, secs = n + k, secs + ns / 1e9
    return trace_reduce.roofline_share(9.0 * ctx.n * n, secs,
                                       ctx.peaks["hbm_bytes_per_s"])
