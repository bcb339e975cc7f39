"""Pallas TPU kernel: masked min-plus stencil for game-map Δ-stepping.

The paper's game-map scenario (Sec. 4) is an occupancy grid whose SSSP
relaxation is an 8-neighbour stencil: straight moves cost 10 (light under
Δ=13), diagonal moves cost 14 (heavy). The paper notes the regular
structure admits SIMD vectorization; the TPU-native version is a
VMEM-tiled stencil sweep over the tentative-distance grid:

    out[r,c] = free[r,c] ? min(tent[r,c],
                min_{(dr,dc) in phase} frontier(tent[r+dr,c+dc])
                                       ? tent[r+dr,c+dc] + cost : INF)
             : INF
    frontier(v) = v < INF and v // Δ == i     (dense bucket array, C1)

Halo handling: the grid is row-blocked; each program instance receives
the block above and below via clamped BlockSpec index maps and masks the
clamp garbage at the grid boundary with ``program_id`` predicates. Column
halos stay inside the block (full-width strips) with INF fill at the
grid edge. The VMEM working set is 5 strips of (block_rows x W) int32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.graphs.structures import INF32

_INF = int(INF32)  # python int: pallas kernels cannot capture traced constants


def _shift_cols(x, dc):
    """Value of the horizontal neighbour at column offset ``dc``; cells
    past the grid edge contribute INF."""
    if dc == 0:
        return x
    fill = jnp.full((x.shape[0], 1), _INF, x.dtype)
    if dc == -1:  # neighbour at c-1
        return jnp.concatenate([fill, x[:, :-1]], axis=1)
    return jnp.concatenate([x[:, 1:], fill], axis=1)


def grid_relax_kernel(i_ref, above_ref, center_ref, below_ref, free_ref,
                      out_ref, *, delta: int, cost_straight: int,
                      cost_diag: int, light: bool, n_blocks: int):
    i = i_ref[0, 0]
    pid = pl.program_id(0)
    a = above_ref[...]
    c = center_ref[...]
    b = below_ref[...]

    # Rows adjacent to the strip; clamp garbage at the grid boundary → INF.
    top = jnp.where(pid == 0, _INF, a[-1:, :])
    bot = jnp.where(pid == n_blocks - 1, _INF, b[:1, :])
    up = jnp.concatenate([top, c[:-1, :]], axis=0)      # value at (r-1, c)
    down = jnp.concatenate([c[1:, :], bot], axis=0)     # value at (r+1, c)

    # Phase membership is static: a move class is relaxed in the light
    # phase iff its cost is <= delta (paper Alg. 1 lines 3-5).
    moves = []
    if (cost_straight <= delta) == light:
        moves += [(up, 0, cost_straight), (down, 0, cost_straight),
                  (c, -1, cost_straight), (c, 1, cost_straight)]
    if (cost_diag <= delta) == light:
        moves += [(up, -1, cost_diag), (up, 1, cost_diag),
                  (down, -1, cost_diag), (down, 1, cost_diag)]

    best = jnp.full_like(c, _INF)
    for base, dc, cost in moves:
        v = _shift_cols(base, dc)
        in_frontier = (v < _INF) & (v // delta == i)    # C1 bucket scan
        cand = jnp.where(in_frontier, v, 0) + cost      # overflow guard
        best = jnp.minimum(best, jnp.where(in_frontier, cand, _INF))

    free = free_ref[...] != 0
    out_ref[...] = jnp.where(free, jnp.minimum(c, best), _INF)


def grid_relax_pallas(tent, free_i8, bucket_i, *, delta: int,
                      cost_straight: int, cost_diag: int, light: bool,
                      block_rows: int, interpret: bool = False):
    """Low-level entry; shapes must already be padded (rows % block_rows
    == 0, cols % 128 == 0). Use ops.grid_relax for the padded wrapper."""
    h, w = tent.shape
    assert h % block_rows == 0, (h, block_rows)
    n_blocks = h // block_rows
    i_arr = jnp.full((1, 1), bucket_i, jnp.int32)
    kernel = functools.partial(
        grid_relax_kernel, delta=delta, cost_straight=cost_straight,
        cost_diag=cost_diag, light=light, n_blocks=n_blocks)
    strip = lambda f: pl.BlockSpec((block_rows, w), f)
    return pl.pallas_call(
        kernel,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            strip(lambda i: (jnp.maximum(i - 1, 0), 0)),   # above
            strip(lambda i: (i, 0)),                       # center
            strip(lambda i: (jnp.minimum(i + 1, n_blocks - 1), 0)),  # below
            strip(lambda i: (i, 0)),                       # free mask
        ],
        out_specs=strip(lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((h, w), jnp.int32),
        interpret=interpret,
        name="grid_relax",
    )(i_arr, tent, tent, tent, free_i8)
