"""Wrapper for frontier_relax: pads the tent slice into (R, 128) lanes
and the ELL block to whole 128-lane tiles, dispatches kernel or oracle,
fills the unwritten slots, flattens/reshapes the outputs."""
from __future__ import annotations

import jax.numpy as jnp

from repro.graphs.structures import INF32
from repro.kernels.frontier_relax.frontier_relax import (
    BLOCK_ROWS,
    frontier_relax_pallas,
)
from repro.kernels.frontier_relax.ref import frontier_relax_ref

_LANE = 128


def lane_padded_width(d: int) -> int:
    """The ELL width the kernel's row DMAs need: ``d`` rounded up to whole
    128-lane tiles."""
    return -(-d // _LANE) * _LANE


def frontier_relax(dist, explored, bucket_i, nbr, w_ell, *, delta: int,
                   cap: int, base=0, sent=None, backend: str = "pallas",
                   interpret: bool = False):
    """Fused frontier scan + compaction + ELL row gather of one bucket.

    dist/explored: int32[S] (the whole tent, or one shard's owned
    slice); nbr/w_ell: int32[S + 1, D] ELL adjacency block (row S
    all-sentinel). Returns ``(fidx int32[cap], rows_n int32[cap, D],
    rows_w int32[cap, D], count int32, any bool, next int32)`` with
    ``fidx`` in global ids (``base`` + local index; padding slots carry
    the global sentinel ``sent``, default S). ``count`` is the full
    bucket population — ``count > cap`` is the caller's overflow flag.

    The kernel needs ``D`` to be a multiple of 128; other widths are
    padded here on every call (with sentinel columns) and the gathered
    rows cut back to ``D``. Callers on the hot path pad once up front
    (``lane_padded_width``).

    A zero-width ELL block (D == 0: no edges on this side of the
    light/heavy split) routes to the jnp oracle — zero-size Pallas
    blocks have no TPU layout, and there is nothing to gather anyway."""
    s = dist.shape[0]
    sent = s if sent is None else sent
    d = w_ell.shape[1]
    if backend == "ref" or d == 0:
        return frontier_relax_ref(dist, explored, bucket_i, nbr, w_ell,
                                  delta=delta, cap=cap, base=base, sent=sent)
    dp = lane_padded_width(d)
    cols = ((0, 0), (0, dp - d))          # cut away again below
    nbr_p = jnp.pad(nbr, cols) if dp != d else nbr
    w_p = jnp.pad(w_ell, cols) if dp != d else w_ell
    per = _LANE * BLOCK_ROWS
    pad = -(-s // per) * per - s
    d2 = jnp.pad(dist, (0, pad), constant_values=INF32).reshape(-1, _LANE)
    e2 = jnp.pad(explored, (0, pad), constant_values=INF32).reshape(-1, _LANE)
    fidx, rows_n, rows_w, scalars = frontier_relax_pallas(
        d2, e2, bucket_i, base, nbr_p, w_p, delta=delta, cap=cap,
        sent=sent, interpret=interpret)
    count = scalars[0]
    # slots past the population were never written: the all-sentinel row
    live = (jnp.arange(cap, dtype=jnp.int32) < jnp.minimum(count, cap))[:, None]
    rows_n = jnp.where(live, rows_n[:, :d], nbr[s][None, :])
    rows_w = jnp.where(live, rows_w[:, :d], w_ell[s][None, :])
    return (fidx.reshape(-1)[:cap], rows_n, rows_w, count, scalars[1] > 0,
            scalars[2])
