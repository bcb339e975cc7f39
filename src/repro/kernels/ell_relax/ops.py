"""Wrapper for the ell_relax kernel: prepares the (n+1, 1) distance
column (sentinel row INF), pads the frontier capacity to a block
multiple, dispatches kernel or oracle."""
from __future__ import annotations

import jax.numpy as jnp

from repro.graphs.structures import INF32
from repro.kernels.ell_relax.ell_relax import ell_relax_pallas
from repro.kernels.ell_relax.ref import ell_relax_ref


def ell_relax(fidx, dist, w_ell, *, backend: str = "pallas",
              rows_per_block: int = 256, interpret: bool = False):
    """Relaxation candidates for a compacted frontier.

    fidx: int32[cap] frontier vertex ids (n = padding sentinel).
    dist: int32[n] tentative distances.
    w_ell: int32[n+1, D] ELL weights (row n all-INF).
    Returns int32[cap, D] candidate distances (INF where invalid).
    ``rows_per_block`` (a multiple of 8) is clamped to the padded cap.
    """
    if backend == "ref":
        return ell_relax_ref(fidx, dist, w_ell)
    n = dist.shape[0]
    cap = fidx.shape[0]
    dist_col = jnp.concatenate(
        [dist, jnp.full((1,), INF32, dist.dtype)])[:, None]   # (n+1, 1)
    # gather rows first (XLA), kernel fuses mask+add
    rows_per_block = min(rows_per_block, -(-cap // 8) * 8)
    pad = (-cap) % rows_per_block
    fidx_p = jnp.concatenate(
        [fidx, jnp.full((pad,), n, fidx.dtype)]) if pad else fidx
    d_rows = jnp.take(dist_col[:, 0], fidx_p, mode="fill",
                      fill_value=INF32)[:, None]
    w_rows = w_ell[fidx_p]
    out = ell_relax_pallas(fidx_p, d_rows, w_rows,
                           rows_per_block=rows_per_block, interpret=interpret)
    return out[:cap]
