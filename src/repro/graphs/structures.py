"""Graph containers used across the framework.

Three layouts, mirroring the paper's data structures and their TPU
adaptations (DESIGN.md §2):

* ``COOGraph`` — flat (src, dst, w) edge arrays. The edge-centric
  Δ-stepping relaxation and the GNN scatter aggregations consume this.
* ``CSRGraph`` — row_ptr/col/w. Host-side construction format; the
  neighbor sampler reads it directly.
* ``ELLGraph`` — padded (n+1, max_deg) neighbor/weight matrices with a
  sentinel row for out-of-frontier gathers. The frontier-centric
  relaxation strategy and the ``ell_relax`` Pallas kernel consume this.

All containers are registered pytrees so they can cross ``jax.jit`` /
``shard_map`` boundaries; static metadata (vertex counts, max degree)
lives in hashable aux data.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

INF32 = np.int32(2**31 - 1)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class COOGraph:
    """Edge-list graph. ``src``/``dst`` int32[E], ``w`` int32[E] >= 0."""

    src: jax.Array
    dst: jax.Array
    w: jax.Array
    n_nodes: int = dataclasses.field(metadata=dict(static=True))

    @property
    def n_edges(self) -> int:
        return int(self.src.shape[0])

    def reversed(self) -> "COOGraph":
        return COOGraph(self.dst, self.src, self.w, self.n_nodes)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class CSRGraph:
    """Compressed sparse row. ``row_ptr`` int32[n+1], ``col``/``w`` int32[E]."""

    row_ptr: jax.Array
    col: jax.Array
    w: jax.Array
    n_nodes: int = dataclasses.field(metadata=dict(static=True))

    @property
    def n_edges(self) -> int:
        return int(self.col.shape[0])

    def degrees(self):
        return self.row_ptr[1:] - self.row_ptr[:-1]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ELLGraph:
    """ELLPACK-padded adjacency with one sentinel row.

    ``nbr``/``w`` have shape (n_nodes + 1, max_deg); invalid slots point at
    the sentinel row ``n_nodes`` with weight ``INF32`` so that a relaxation
    through them can never win a scatter-min. Row ``n_nodes`` itself is all
    sentinel, which makes gathers with out-of-range (padded) frontier
    indices harmless — the same trick the paper uses with its fixed-size
    bucket array (no synchronization, garbage writes are benign).
    """

    nbr: jax.Array
    w: jax.Array
    n_nodes: int = dataclasses.field(metadata=dict(static=True))
    max_deg: int = dataclasses.field(metadata=dict(static=True))

    @property
    def valid(self):
        return self.nbr != self.n_nodes


def coo_to_csr(g: COOGraph) -> CSRGraph:
    """Host-side COO→CSR (numpy; part of the data pipeline, not the jit path)."""
    src = np.asarray(g.src)
    dst = np.asarray(g.dst)
    w = np.asarray(g.w)
    order = np.argsort(src, kind="stable")
    src, dst, w = src[order], dst[order], w[order]
    counts = np.bincount(src, minlength=g.n_nodes).astype(np.int32)
    row_ptr = np.zeros(g.n_nodes + 1, dtype=np.int32)
    np.cumsum(counts, out=row_ptr[1:])
    return CSRGraph(
        row_ptr=jnp.asarray(row_ptr),
        col=jnp.asarray(dst.astype(np.int32)),
        w=jnp.asarray(w.astype(np.int32)),
        n_nodes=g.n_nodes,
    )


def csr_to_ell(g: CSRGraph, max_deg: int | None = None) -> ELLGraph:
    """Pad a CSR graph to ELL. Rows longer than ``max_deg`` are an error —
    callers choose truncation policies explicitly (we never silently drop
    edges of an SSSP instance)."""
    row_ptr = np.asarray(g.row_ptr)
    col = np.asarray(g.col)
    w = np.asarray(g.w)
    n = g.n_nodes
    deg = row_ptr[1:] - row_ptr[:-1]
    d = int(deg.max()) if deg.size else 0
    if max_deg is None:
        max_deg = max(d, 1)
    if d > max_deg:
        raise ValueError(f"max degree {d} exceeds ELL width {max_deg}")
    nbr = np.full((n + 1, max_deg), n, dtype=np.int32)
    ww = np.full((n + 1, max_deg), INF32, dtype=np.int32)
    # slot index of every edge within its row
    slot = np.arange(col.shape[0], dtype=np.int64) - row_ptr[:-1].repeat(deg)
    row = np.arange(n, dtype=np.int64).repeat(deg)
    nbr[row, slot] = col
    ww[row, slot] = w
    return ELLGraph(jnp.asarray(nbr), jnp.asarray(ww), n, max_deg)


def union_with_reverse(g: COOGraph) -> COOGraph:
    """Disjoint union of ``g`` with its edge-reversed copy: vertices
    ``0..n-1`` carry the original graph, vertices ``n..2n-1`` carry the
    reversed one (edge ``(u, v, w)`` also appears as ``(v+n, u+n, w)``).
    The two halves share no edges, so one Δ-stepping solve seeded at
    ``s`` (forward half) and ``t+n`` (reversed half) runs a forward and
    a backward search in bucket lockstep — the substrate of the
    bidirectional point-to-point modes (repro.landmarks, DESIGN.md §14).
    Host-side preprocessing (numpy), weight-independent up to the shared
    ``w`` array."""
    src = np.asarray(g.src)
    dst = np.asarray(g.dst)
    w = np.asarray(g.w)
    n = g.n_nodes
    return COOGraph(
        jnp.asarray(np.concatenate([src, dst + n]).astype(np.int32)),
        jnp.asarray(np.concatenate([dst, src + n]).astype(np.int32)),
        jnp.asarray(np.concatenate([w, w]).astype(np.int32)),
        2 * n,
    )


def light_heavy_split(g: CSRGraph, delta: int) -> Tuple[CSRGraph, CSRGraph]:
    """Paper Alg. 1 lines 3–5: split outgoing edges into light (w <= Δ) and
    heavy (w > Δ) CSR structures. Host-side preprocessing for the ELL
    strategies; the edge-centric path splits its COO arrays on the device
    instead (``core.backends.EdgeBackend``, DESIGN.md §3)."""
    row_ptr = np.asarray(g.row_ptr)
    col = np.asarray(g.col)
    w = np.asarray(g.w)
    n = g.n_nodes
    deg = row_ptr[1:] - row_ptr[:-1]
    row = np.arange(n, dtype=np.int64).repeat(deg)
    light = w <= delta

    def build(mask):
        counts = np.bincount(row[mask], minlength=n).astype(np.int32)
        rp = np.zeros(n + 1, dtype=np.int32)
        np.cumsum(counts, out=rp[1:])
        return CSRGraph(jnp.asarray(rp), jnp.asarray(col[mask]),
                        jnp.asarray(w[mask]), n)

    return build(light), build(~light)
