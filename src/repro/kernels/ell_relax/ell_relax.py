"""Pallas TPU kernel: frontier-row expansion for the ELL strategy.

The frontier-centric Δ-stepping sweep gathers the ELL adjacency rows of
the compacted frontier and produces relaxation candidates
``tent[v] + w(v, u)`` (paper's request-set computation, with the C4
deviation of evaluating costs during generation). The gather itself is
an XLA gather in ``ops.py``; this kernel fuses the validity mask and
the add over blocks of gathered rows. (A one-row-per-step variant that
let the scalar-prefetched index drive each row's DMA is not possible:
a (1, D) block does not divide the (8, 128) tiling the TPU compiler
requires of VMEM blocks.)

Grid: one step per block of ``rows_per_block`` frontier entries; padding
entries point at the all-sentinel row ``n`` and yield INF candidates.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.graphs.structures import INF32

_INF = int(INF32)  # python int: pallas kernels cannot capture traced constants


def ell_relax_kernel(fidx_ref, dist_ref, w_ref, out_ref):
    """dist_ref: (R, 1) tent distances of this block's frontier rows;
    w_ref: (R, D) edge weights (INF = padding slot); out: candidates."""
    d = dist_ref[...]                       # (R, 1)
    w = w_ref[...]                          # (R, D)
    valid = (w < _INF) & (d < _INF)
    cand = jnp.where(valid, d, 0) + jnp.where(valid, w, 0)
    out_ref[...] = jnp.where(valid, cand, _INF)


def ell_relax_pallas(fidx, dist_col, w_ell, *, rows_per_block: int,
                     interpret: bool = False):
    """fidx: int32[cap] compacted frontier (sentinel n for padding);
    dist_col: int32[n+1, 1] tent distances (row n = INF sentinel);
    w_ell: int32[n+1, D] ELL weights. Returns candidates int32[cap, D].

    cap % rows_per_block == 0 is required (ops.py pads).
    """
    cap = fidx.shape[0]
    d = w_ell.shape[1]
    assert cap % rows_per_block == 0
    n_blocks = cap // rows_per_block

    def dist_map(i, fidx_ref):
        del fidx_ref
        return (i, 0)

    # The rows arrive already gathered by ops.py (an XLA gather): a
    # one-row block per frontier entry would not divide the (8, 128)
    # tiling the TPU compiler requires of VMEM blocks.
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((rows_per_block, 1), dist_map),
            pl.BlockSpec((rows_per_block, d), dist_map),
        ],
        out_specs=pl.BlockSpec((rows_per_block, d), dist_map),
    )
    return pl.pallas_call(
        ell_relax_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((cap, d), jnp.int32),
        interpret=interpret,
    )(fidx, dist_col, w_ell)

