"""Pallas TPU kernel: fused bucket-array scan.

The paper's C1 trade: the dense bucket array must be *scanned in full*
at every inner iteration to find the members of the current bucket. In
the JAX engine that scan shows up three times per iteration (frontier
mask, frontier-any termination flag, next-bucket minimum). This kernel
fuses all three into one pass over ``tent``/``explored``:

    frontier[v]   = tent[v] < INF  &  tent[v]//Δ == i  &  tent[v] < explored[v]
    any_frontier  = OR-reduce(frontier)
    next_bucket   = min over v of tent[v]//Δ restricted to buckets > i
                    and unsettled vertices (tent[v] < explored[v]) —
                    bitwise identical on cold solves, and the rule that
                    lets warm re-solves (repro.dynamic) skip buckets the
                    repair never touched (DESIGN.md §11)

Grid is 1-D over row blocks of the (padded) lane layout of tent. The
bucket index comes in and the two scalar results (``any``, ``next``) go
out through SMEM: the TPU compiler stores scalars only to SMEM, never
to VMEM. The scalars accumulate across the sequential grid steps
(``"arbitrary"`` semantics), which keeps the accumulation race-free —
the same argument the paper makes for benign writes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.graphs.structures import INF32

_INF = int(INF32)  # python int: pallas kernels cannot capture traced constants
_IMAX = 2**31 - 1


def bucket_scan_kernel(i_ref, tent_ref, explored_ref, frontier_ref, out_ref,
                       *, delta: int):
    pid = pl.program_id(0)
    i = i_ref[0]
    t = tent_ref[...]
    e = explored_ref[...]
    fin = t < _INF
    b = jnp.where(fin, t // delta, _IMAX)
    f = fin & (b == i) & (t < e)
    frontier_ref[...] = f.astype(jnp.int8)

    @pl.when(pid == 0)
    def _init():
        # explicit int32: under x64 a weak python int would store as int64
        out_ref[0] = jnp.int32(0)
        out_ref[1] = jnp.int32(_IMAX)

    out_ref[0] = jnp.maximum(out_ref[0], jnp.max(f.astype(jnp.int32)))
    nb = jnp.min(jnp.where((b > i) & (t < e), b, _IMAX)).astype(jnp.int32)
    out_ref[1] = jnp.minimum(out_ref[1], nb)


def bucket_scan_pallas(tent2d, explored2d, bucket_i, *, delta: int,
                       block_rows: int, interpret: bool = False):
    """tent2d/explored2d: int32[R, 128] padded row-major reshape of tent
    (padding = INF). Returns (frontier int8[R,128], scalars int32[2] =
    (any, next_bucket))."""
    r, lanes = tent2d.shape
    assert r % block_rows == 0
    n_blocks = r // block_rows
    i_arr = jnp.reshape(jnp.asarray(bucket_i, jnp.int32), (1,))
    kernel = functools.partial(bucket_scan_kernel, delta=delta)
    blk = pl.BlockSpec((block_rows, lanes), lambda i: (i, 0))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        kernel,
        grid=(n_blocks,),
        in_specs=[smem, blk, blk],
        out_specs=[blk, smem],
        out_shape=[
            jax.ShapeDtypeStruct((r, lanes), jnp.int8),
            jax.ShapeDtypeStruct((2,), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(i_arr, tent2d, explored2d)
