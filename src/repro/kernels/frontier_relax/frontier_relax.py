"""Pallas TPU kernel: fused frontier scan + compaction + ELL row gather.

The paper's bucket-fusion deviation taken one step further (DESIGN.md
§12): the ``bucket_scan`` pass (frontier mask / any-reduce / next-bucket
min), the frontier compaction (``jnp.nonzero``) and the ELL row gather
of ``ell_sweep`` are three separate XLA ops in the ``ell`` strategy —
three full passes over HBM-resident arrays per inner iteration. This
kernel runs all of them in ONE ``pallas_call``, one grid step per
(8, 128) block of the tent/explored lane layout:

  phase A (vector): frontier flags of bucket ``i`` restricted to
      unsettled vertices (``dist < explored``), their any-reduce, and
      the next-bucket minimum — the exact ``scan_bucket`` formulas, so
      the scalar outputs are bitwise those of the jnp twin;
  phase B (per hit): the block's flags are popped in ascending order
      (a vector min-reduce over the block's local indices per hit), so
      the compacted buffer has the same first-``cap`` truncation order
      as ``jnp.nonzero(size=cap)``. The *total* population is counted
      past the cap (the overflow signal). Blocks without a hit cost
      only phase A;
  phase C (per hit): the hit's ELL neighbor and weight rows are copied
      HBM → HBM by DMA into slot ``pos`` of the gathered outputs; at
      most ``_DMA_WINDOW`` row pairs are in flight.

The ELL blocks stay in HBM (``memory_space=pl.ANY``): only the gathered
rows move, so the kernel's VMEM use does not grow with |E|. A DMA slice
must span whole 128-lane tiles, so the ELL width must be a multiple of
128 (``ops.frontier_relax`` pads; the fused backends pad once at build
time). Scalars live in SMEM — the TPU compiler stores scalars only
there. The compacted index buffer is a lane-dense VMEM output written
one 128-lane row at a time; it is the one VMEM buffer that grows with
``cap`` (4 bytes per slot).

Slots past the frontier population are left unwritten by the kernel;
``ops.frontier_relax`` fills them with the all-sentinel row (INF
weights — the 'benign garbage' idiom every consumer already handles).

The kernel stays int32-distance-only like ``ell_relax``: packing and
the C4 filter happen in XLA on the gathered rows (the shared
``ell_relax_words`` path), which is what keeps ``fused`` bitwise
interchangeable with ``edge``/``ell`` for packed (cost, pred) words.
``base`` lifts slice-local compacted indices to global vertex ids for
the sharded variant; the global padding sentinel is ``sent``.
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
_LANE = 128
BLOCK_ROWS = 8        # one (8, 128) int32 vreg of tent per grid step
_DMA_WINDOW = 16      # row-pair DMAs in flight per block
VMEM_LIMIT = 96 * 2**20


def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def frontier_relax_kernel(sc_ref, dist_ref, explored_ref, nbr_hbm, w_hbm,
                          fidx_ref, rows_n_hbm, rows_w_hbm, out_ref, cnt_ref,
                          sem, *, delta: int, cap: int, sent: int):
    pid = pl.program_id(0)
    i = sc_ref[0]
    base = sc_ref[1]

    @pl.when(pid == 0)
    def _init():
        cnt_ref[0] = jnp.int32(0)
        out_ref[1] = jnp.int32(0)
        out_ref[2] = jnp.int32(_IMAX)
        fidx_ref[...] = jnp.full(fidx_ref.shape, sent, jnp.int32)

    # -- phase A: the scan_bucket formulas on this block --
    t = dist_ref[...]                       # (8, 128), padding = INF
    e = explored_ref[...]
    fin = t < _INF
    b = jnp.where(fin, t // delta, _IMAX)
    f = (fin & (b == i) & (t < e)).astype(jnp.int32)
    k = jnp.sum(f, dtype=jnp.int32)
    out_ref[1] = jnp.maximum(out_ref[1], jnp.minimum(k, 1))
    nb = jnp.min(jnp.where((b > i) & (t < e), b, _IMAX))
    out_ref[2] = jnp.minimum(out_ref[2], nb.astype(jnp.int32))

    # -- phases B + C: pop the block's hits in ascending order --
    pos0 = cnt_ref[0]
    cnt_ref[0] = pos0 + k                   # count past cap: overflow signal
    n_put = jnp.clip(cap - pos0, 0, k)
    blk0 = pid * (BLOCK_ROWS * _LANE)
    local = _iota(f.shape, 0) * _LANE + _iota(f.shape, 1)
    lane = _iota((1, _LANE), 1)

    def copy(src, dst, r_src, r_dst):
        return pltpu.make_async_copy(src.at[pl.ds(r_src, 1)],
                                     dst.at[pl.ds(r_dst, 1)], sem)

    def wait_pair():
        copy(nbr_hbm, rows_n_hbm, 0, 0).wait()
        copy(w_hbm, rows_w_hbm, 0, 0).wait()

    def hit(j, rest):
        m = jnp.min(jnp.where(rest > 0, local, _IMAX))
        lidx = blk0 + m
        pos = pos0 + j
        r = pos // _LANE
        row = fidx_ref[pl.ds(r, 1), :]
        fidx_ref[pl.ds(r, 1), :] = jnp.where(lane == pos % _LANE,
                                             lidx + base, row)
        copy(nbr_hbm, rows_n_hbm, lidx, pos).start()
        copy(w_hbm, rows_w_hbm, lidx, pos).start()
        pl.when(j >= _DMA_WINDOW)(wait_pair)
        return jnp.where(local == m, 0, rest)

    @pl.when(n_put > 0)
    def _gather():
        jax.lax.fori_loop(0, n_put, hit, f)

        def drain(j, carry):
            wait_pair()
            return carry

        jax.lax.fori_loop(0, jnp.minimum(n_put, _DMA_WINDOW), drain, 0)

    @pl.when(pid == pl.num_programs(0) - 1)
    def _finish():
        out_ref[0] = cnt_ref[0]


def frontier_relax_pallas(dist2d, explored2d, bucket_i, base, nbr, w_ell, *,
                          delta: int, cap: int, sent: int,
                          interpret: bool = False):
    """dist2d/explored2d: int32[R, 128] padded lane reshape of the tent
    slice (padding = INF, R a multiple of ``BLOCK_ROWS``); nbr/w_ell:
    int32[n_rows + 1, D] ELL block in HBM, D a multiple of 128. Returns
    ``(fidx int32[ceil(cap/128), 128], rows_n int32[cap, D],
    rows_w int32[cap, D], scalars int32[3] = (count, any, next))``;
    ``fidx`` slots past the count hold ``sent``, ``rows_*`` slots past
    it are unwritten."""
    r, lanes = dist2d.shape
    assert lanes == _LANE and r % BLOCK_ROWS == 0, dist2d.shape
    d = w_ell.shape[1]
    assert d % _LANE == 0, f"ELL width {d} must be a multiple of {_LANE}"
    cap_rows = -(-cap // _LANE)
    fidx_bytes = cap_rows * _LANE * 4
    if fidx_bytes + 8 * 2**20 > VMEM_LIMIT:
        raise ValueError(
            f"frontier_relax keeps its compacted index buffer in VMEM: "
            f"cap={cap} needs {fidx_bytes} bytes, over the kernel's "
            f"{VMEM_LIMIT}-byte VMEM budget")
    scalars = jnp.stack([jnp.asarray(bucket_i, jnp.int32),
                         jnp.asarray(base, jnp.int32)])
    kernel = functools.partial(frontier_relax_kernel, delta=delta, cap=cap,
                               sent=sent)
    blk = pl.BlockSpec((BLOCK_ROWS, _LANE), lambda i: (i, 0))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    return pl.pallas_call(
        kernel,
        grid=(r // BLOCK_ROWS,),
        in_specs=[smem, blk, blk, hbm, hbm],
        out_specs=[vmem, hbm, hbm, smem],
        out_shape=[
            jax.ShapeDtypeStruct((cap_rows, _LANE), jnp.int32),
            jax.ShapeDtypeStruct((cap, d), jnp.int32),
            jax.ShapeDtypeStruct((cap, d), jnp.int32),
            jax.ShapeDtypeStruct((3,), jnp.int32),
        ],
        scratch_shapes=[pltpu.SMEM((1,), jnp.int32),
                        pltpu.SemaphoreType.DMA],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="frontier_relax",
    )(scalars, dist2d, explored2d, nbr, w_ell)
