"""Wrapper for bucket_scan: reshapes the 1-D tent vector into padded
(R, 128) lanes, dispatches kernel or oracle, flattens the result."""
from __future__ import annotations

import jax.numpy as jnp

from repro.graphs.structures import INF32
from repro.kernels.bucket_scan.bucket_scan import bucket_scan_pallas
from repro.kernels.bucket_scan.ref import bucket_scan_ref

_LANE = 128


def bucket_scan(tent, explored, bucket_i, *, delta: int,
                block_rows: int = 256, backend: str = "pallas",
                interpret: bool = False):
    """Fused frontier mask + frontier-any + next-bucket scan.

    tent, explored: int32[n]. Returns (frontier bool[n], any bool,
    next_bucket int32 scalar). ``block_rows`` (a multiple of 8) is the
    grid step in 128-lane rows, clamped to the padded vector so small
    inputs are not padded to a whole block."""
    if backend == "ref":
        return bucket_scan_ref(tent, explored, bucket_i, delta=delta)
    n = tent.shape[0]
    block_rows = min(block_rows, -(-n // (_LANE * 8)) * 8)
    per = _LANE * block_rows
    npad = -(-n // per) * per
    pad = npad - n
    t2 = jnp.pad(tent, (0, pad), constant_values=INF32).reshape(-1, _LANE)
    e2 = jnp.pad(explored, (0, pad), constant_values=INF32).reshape(-1, _LANE)
    f2, scalars = bucket_scan_pallas(t2, e2, bucket_i, delta=delta,
                                     block_rows=block_rows,
                                     interpret=interpret)
    frontier = (f2.reshape(-1) != 0)[:n]
    return frontier, scalars[0] > 0, scalars[1]
