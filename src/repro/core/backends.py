"""Pluggable relaxation backends for the unified Δ-stepping driver.

DESIGN.md §3: the paper's three inner mechanisms — request generation
(light/heavy split), relaxation, and the dense bucket scan (C1) — are
isolated behind the ``RelaxBackend`` protocol so a single generic
outer/inner loop driver (``core.delta_stepping``) hosts every strategy:

* ``edge``   — edge-centric sweep (jnp scatter-min) over the edge
  array of the phase: light and heavy halves split once at build.
* ``ell``    — frontier-compacted expansion of light/heavy ELL blocks
  (jnp); work scales with |frontier|·max_deg.
* ``pallas`` — the same ELL expansion through the ``kernels/ell_relax``
  Pallas kernel with bucket bookkeeping fused by ``kernels/bucket_scan``;
  on game-map (occupancy-grid) instances the relaxation is instead the
  ``kernels/grid_relax`` min-plus stencil.
* ``fused``  — the frontier-compacted ELL expansion with scan,
  compaction and row gather fused into one ``kernels/frontier_relax``
  Pallas call (DESIGN.md §12); the backend additionally implements the
  driver's *fused light phase* protocol (``supports_fused_light`` /
  ``fused_iter`` / ``fused_next``), so each inner iteration is one
  kernel step plus O(cap·deg) XLA scatters instead of three full-width
  passes. Off TPU the kernel runs only where the config chooses so
  explicitly: under the Pallas interpreter (``interpret=True``) or as
  its bitwise-identical jnp twin (``kernel_twin=True``); see
  ``kernel_path``.
* ``sharded_edge`` / ``sharded_ell`` — SPMD variants of the first two:
  edges (or ELL row blocks) are partitioned across a 1-D device mesh
  (``graphs.partition``), each sweep runs per-shard under ``shard_map``
  (through ``compat``) and merges candidates with an all-reduce min.
  The merge reduces whole tent *words* — in ``packed`` mode the int64
  (cost, pred) word — so the sharded run is bitwise identical to the
  single-device engine, not merely distance-equal (DESIGN.md §9).
* ``sharded_fused`` — the fused step per shard (each device scans and
  compacts its owned vertex slice and gathers its local ELL rows),
  composed with exactly the same all-reduce min-over-words merge, so
  both contracts hold at once: bitwise ≡ single-device ``fused`` for
  any shard count, and ``fused`` bitwise ≡ ``edge``/``ell``.

A backend provides two traced operations over solver state:

  ``sweep(tent, mask, bucket_i, light=, packed=)`` → ``(tent', overflow)``
      one relaxation sweep from the masked vertex set (the current
      frontier for light passes, the settled set S for the heavy pass);
  ``scan(dist, explored, bucket_i)`` → ``(frontier, any, next_bucket)``
      the fused dense-bucket scan.

plus host-side preprocessing in its ``build`` classmethod (CSR
conversion, light/heavy split, ELL padding). Backends are registered
pytrees: their operand arrays are jit *arguments*, not baked constants,
so solvers over same-shaped graphs share compile cache entries.

Sweeps are pure scatter-min dataflow, so the driver can ``vmap`` them
over a batch of sources (``supports_vmap``); the Pallas-backed ones run
the batch under ``lax.map`` instead (``pallas_call`` with scalar-prefetch
grids has no batching rule).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec

from repro import compat
from repro.core import pack as packing
from repro.graphs.partition import ELLPartition, partition_edges, partition_ell
from repro.graphs.structures import (
    COOGraph,
    ELLGraph,
    INF32,
    coo_to_csr,
    csr_to_ell,
    light_heavy_split,
)
from repro.kernels.bucket_scan import bucket_scan
from repro.kernels.ell_relax import ell_relax
from repro.kernels.frontier_relax import frontier_relax
from repro.kernels.frontier_relax.ops import lane_padded_width
from repro.kernels.grid_relax import grid_relax

_IMAX = jnp.int32(2**31 - 1)


# ---------------------------------------------------------------------------
# value-word helpers: every backend is generic over 'plain int32 distance'
# vs 'packed int64 (distance, predecessor)' words (paper C3, pack.py).
# ---------------------------------------------------------------------------

def init_tent(n: int, source, packed: bool):
    if packed:
        tent = jnp.full((n,), packing.INF_PACKED, dtype=jnp.int64)
        src_word = packing.pack(jnp.zeros((), jnp.int32),
                                jnp.asarray(source, jnp.int32))
        return tent.at[source].set(src_word)
    return jnp.full((n,), INF32, jnp.int32).at[source].set(0)


def dist_of(tent, packed: bool):
    return packing.unpack_dist(tent) if packed else tent


def candidate_words(cand_d, src_ids, ok, packed: bool):
    if packed:
        word = packing.pack(cand_d, src_ids)
        return jnp.where(ok, word, packing.INF_PACKED)
    return jnp.where(ok, cand_d, INF32)


def graph_is_canonical(graph: COOGraph) -> bool:
    """True when every edge weight is >= 1 — the *canonical-ties* graph
    class (DESIGN.md §11). On it, packed (cost, pred) relaxations use
    the word-order C4 filter, whose fixed point is a pure function of
    (graph, source): word[v] = (dist[v], smallest-id tight parent). That
    trajectory independence is what lets a warm-started repair solve
    (repro.dynamic) be bitwise identical to a cold solve. Zero-weight
    graphs keep the historical strict-distance filter instead: the
    canonical rule could close a predecessor cycle inside a zero-weight
    tie group (exactly the hazard documented for pred_mode='argmin'),
    while the temporal first-settled tie-break cannot."""
    w = np.asarray(graph.w)
    return bool(w.size == 0 or int(w.min()) >= 1)


# ---------------------------------------------------------------------------
# shared primitive ops (also consumed by core.distributed)
# ---------------------------------------------------------------------------

def scan_bucket(dist, explored, bucket_i, *, delta: int):
    """Fused dense-bucket scan (paper C1): the frontier mask of bucket
    ``bucket_i``, its any-reduce, and the next bucket index holding
    *unexplored* work — pure-jnp twin of the ``kernels/bucket_scan``
    Pallas kernel.

    The next-bucket minimum is restricted to unsettled vertices
    (``dist < explored``). On a cold solve this changes nothing, bit
    for bit: while the outer loop sits at bucket i, every finite vertex
    in a bucket > i is still unexplored (``explored`` is only ever set
    to a vertex's tent value while it sits in the *current* bucket's
    frontier, and tent never increases — so a future-bucket tent value
    is always strictly below its explored mark; proof in DESIGN.md
    §11). On a *warm* re-solve (repro.dynamic) the restriction is
    load-bearing: buckets whose vertices are all pre-settled from the
    previous solve are skipped outright, which bounds the outer loop to
    the buckets the repair actually touched."""
    fin = dist < INF32
    b = jnp.where(fin, dist // delta, _IMAX)
    frontier = fin & (b == bucket_i) & (dist < explored)
    nxt = jnp.where((b > bucket_i) & (dist < explored), b, _IMAX).min()
    return frontier, frontier.any(), nxt


def edge_candidates(d_src, f_src, w, *, delta: int, light: bool):
    """Candidate distances of one edge-array relaxation and the C4 early
    mask (frontier membership + phase; the ``cand < tent[dst]`` filter is
    the caller's, since only it holds the destination gather)."""
    active = f_src & (d_src < INF32)
    cand = jnp.where(active, d_src, 0) + jnp.where(active, w, 0)
    phase = (w <= delta) if light else (w > delta)
    return cand, active & phase


def edge_relax_words(tent, frontier, src, dst, w, *, delta: int, light: bool,
                     packed: bool, canonical: bool = False):
    """Candidate words of one edge-array relaxation: frontier/phase mask,
    C4 early filter against the destination gather, word packing. The
    single shared generation path of the single-device ``edge_sweep``
    and the per-shard ``ShardedEdgeBackend`` sweep — callers differ only
    in the scatter target (tent vs a per-shard merge buffer), which is
    what keeps them bitwise interchangeable (DESIGN.md §9). Padding
    edges may carry src == n (sentinel): out-of-range gathers are filled
    inactive — the TPU version of the paper's 'benign garbage writes'
    argument.

    The C4 filter has two regimes (DESIGN.md §11): with
    ``canonical=True`` (packed mode on a w >= 1 graph) a candidate word
    passes when it beats the destination's current *word* — so an
    equal-cost candidate with a smaller predecessor id still lands, and
    the converged word is the schedule-independent (dist, smallest-id
    tight parent). Otherwise the historical strict distance comparison
    applies (first-settled tie winner keeps its slot)."""
    d = dist_of(tent, packed)
    f = jnp.take(frontier, src, mode="fill", fill_value=False)
    d_src = jnp.take(d, src, mode="fill", fill_value=INF32)
    cand, ok = edge_candidates(d_src, f, w, delta=delta, light=light)
    if packed and canonical:
        word = packing.pack(cand, src)
        word_dst = jnp.take(tent, dst, mode="fill",
                            fill_value=packing.INF_PACKED)
        ok = ok & (word < word_dst)       # C4 on (cost, pred) word order
        return jnp.where(ok, word, packing.INF_PACKED)
    d_dst = jnp.take(d, dst, mode="fill", fill_value=INF32)
    ok = ok & (cand < d_dst)              # C4: early filter before scatter
    return candidate_words(cand, src, ok, packed)


def edge_sweep(tent, frontier, src, dst, w, *, delta: int, light: bool,
               packed: bool, canonical: bool = False):
    """One relaxation sweep over an edge array; out-of-range scatters
    (padding edges) drop."""
    words = edge_relax_words(tent, frontier, src, dst, w,
                             delta=delta, light=light, packed=packed,
                             canonical=canonical)
    return tent.at[dst].min(words, mode="drop")


def ell_relax_words(tent, fidx, rows_n, rows_w, *, n: int, packed: bool,
                    canonical: bool = False):
    """Candidate words of gathered ELL rows (``rows_n``/``rows_w``
    (cap, D), global neighbor ids). ``fidx`` int32[cap] holds the
    *global* vertex ids of the compacted rows with a >= n sentinel for
    padding slots (gathers INF). Shared by the single-device
    ``ell_sweep`` and the per-shard ``ShardedEllBackend`` sweep — same
    bitwise-interchangeability contract (and the same two C4 filter
    regimes) as ``edge_relax_words``."""
    d = dist_of(tent, packed)
    d_f = jnp.take(d, fidx, mode="fill", fill_value=INF32)
    valid = (rows_n < n) & (rows_w < INF32) & (d_f[:, None] < INF32)
    cand = (jnp.where(valid, d_f[:, None], 0)
            + jnp.where(valid, rows_w, 0))
    src_ids = jnp.broadcast_to(fidx[:, None], rows_n.shape)
    if packed and canonical:
        word = packing.pack(cand, src_ids)
        word_dst = jnp.take(tent, rows_n, mode="fill",
                            fill_value=packing.INF_PACKED)
        ok = valid & (word < word_dst)
        return jnp.where(ok, word, packing.INF_PACKED)
    d_dst = jnp.take(d, rows_n, mode="fill", fill_value=INF32)
    ok = valid & (cand < d_dst)
    return candidate_words(cand, src_ids, ok, packed)


def ell_sweep(tent, fidx, nbr, w_ell, *, n: int, packed: bool,
              canonical: bool = False):
    """Expand compacted frontier rows of an ELL adjacency block.
    ``fidx`` int32[cap] with sentinel value n for padding slots."""
    rows_n = nbr[fidx]                      # (cap, D); row n is all-sentinel
    rows_w = w_ell[fidx]
    words = ell_relax_words(tent, fidx, rows_n, rows_w, n=n, packed=packed,
                            canonical=canonical)
    return tent.at[rows_n.ravel()].min(words.ravel(), mode="drop")


# ---------------------------------------------------------------------------
# backends
# ---------------------------------------------------------------------------

class RelaxBackend:
    """Strategy protocol consumed by the unified driver (methods only;
    concrete backends are frozen pytree dataclasses)."""

    supports_vmap = True
    delta: int
    # how the strategy's Pallas kernels execute: 'xla' (no kernel),
    # 'compiled', 'interpret' or 'twin' (see ``kernel_path``)
    kernel_path = "xla"

    def sweep(self, tent, mask, bucket_i, *, light: bool, packed: bool):
        raise NotImplementedError

    @jax.named_scope("bucket_scan")
    def scan(self, dist, explored, bucket_i):
        return scan_bucket(dist, explored, bucket_i, delta=self.delta)


def _static():
    return dataclasses.field(metadata=dict(static=True))


KERNEL_STRATEGIES = ("pallas", "fused", "sharded_fused")


def kernel_path(cfg) -> str:
    """How a kernel strategy's Pallas kernels execute here, decided once
    when the backend is built and reported by ``Plan.explain()``:

    * ``'compiled'`` — on a TPU, always: no kernel strategy runs the
      interpreter or the jnp twin there, and asking for either is an
      error rather than a silent swap;
    * ``'interpret'`` — off TPU with ``interpret=True`` (the CPU test
      configuration: the kernel body runs in the Pallas interpreter);
    * ``'twin'`` — off TPU with ``kernel_twin=True`` ('fused' /
      'sharded_fused' only): the bitwise-identical jnp twin of
      ``kernels/frontier_relax``.

    Off TPU without either choice the build fails: Pallas TPU kernels
    compile only for a TPU, and nothing picks a substitute for the
    caller. Non-kernel strategies return ``'xla'`` and ignore both
    flags."""
    if cfg.strategy not in KERNEL_STRATEGIES:
        return "xla"
    if cfg.kernel_twin and cfg.strategy == "pallas":
        raise ValueError("strategy='pallas' has no jnp twin; off TPU use "
                         "interpret=True")
    if cfg.interpret and cfg.kernel_twin:
        raise ValueError("interpret=True and kernel_twin=True are two "
                         "different kernel paths; choose one")
    if jax.default_backend() == "tpu":
        if cfg.interpret or cfg.kernel_twin:
            raise ValueError(
                f"strategy={cfg.strategy!r} runs its compiled Pallas "
                "kernels on TPU; interpret=True / kernel_twin=True are "
                "off-TPU choices")
        return "compiled"
    if cfg.interpret:
        return "interpret"
    if cfg.kernel_twin:
        return "twin"
    raise ValueError(
        f"strategy={cfg.strategy!r} compiles its Pallas kernels only for "
        f"a TPU (this backend is {jax.default_backend()!r}); choose "
        "interpret=True (Pallas interpreter)"
        + ("" if cfg.strategy == "pallas"
           else " or kernel_twin=True (bitwise jnp twin)"))


class _FrontierCompactMixin:
    """Shared ELL-strategy frontier compaction: masked vertex set → a
    fixed-capacity index buffer (sentinel ``n``) plus the overflow flag.
    Consumers declare static fields ``n`` and ``cap``."""

    def compact(self, mask):
        idx = jnp.nonzero(mask, size=self.cap,
                          fill_value=self.n)[0].astype(jnp.int32)
        return idx, mask.sum() > self.cap


class _PallasScanMixin:
    """Bucket bookkeeping on the fused ``kernels/bucket_scan`` Pallas
    kernel. Consumers declare static fields ``delta`` and
    ``kernel_path``."""

    @jax.named_scope("bucket_scan")
    def scan(self, dist, explored, bucket_i):
        return bucket_scan(dist, explored, bucket_i, delta=self.delta,
                           backend="pallas",
                           interpret=self.kernel_path == "interpret")


def _ell_blocks(graph: COOGraph, delta: int, max_deg=None):
    """Host-side preprocessing shared by the ELL strategies: CSR convert,
    light/heavy split (paper Alg. 1 lines 3–5), ELL pad. ``max_deg``
    pins both blocks' pad width; the default (tightest per-block width)
    is weight-*dependent* — dynamic-update consumers pin the weight-
    independent full adjacency degree instead, so rebuilding after a
    cost change keeps the compiled shapes (repro.dynamic)."""
    csr = coo_to_csr(graph)
    light, heavy = light_heavy_split(csr, delta)
    return csr_to_ell(light, max_deg), csr_to_ell(heavy, max_deg)


@partial(jax.jit, static_argnames=("delta", "n", "n_light", "n_heavy"))
def _split_edges(src, dst, w, *, delta: int, n: int, n_light: int,
                 n_heavy: int):
    """Stable light/heavy partition of an edge array on the device
    (paper Alg. 1 lines 3–5): the light half (``w <= delta``) and the
    heavy half, each in the original edge order, of ``n_light`` and
    ``n_heavy`` slots. Slots past a half's edges are sentinel edges
    (``src = dst = n``, ``w = INF32``), which every sweep's gathers fill
    inactive and its scatter drops."""
    light = w <= delta
    m = w.shape[0]

    def half(mask, size):
        idx = jnp.nonzero(mask, size=size, fill_value=m)[0]
        return (jnp.take(src, idx, mode="fill", fill_value=n),
                jnp.take(dst, idx, mode="fill", fill_value=n),
                jnp.take(w, idx, mode="fill", fill_value=INF32))

    return half(light, n_light) + half(~light, n_heavy)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class EdgeBackend(RelaxBackend):
    """Edge-centric strategy: the edges are split once at build into a
    light half (``w <= delta``) and a heavy half, and each sweep touches
    every edge of its phase, masked by frontier membership of their
    source (fixed shapes, no compaction). ``capacity`` is the slot count
    both halves are padded to with sentinel edges (``Plan.update``
    rebuilds at capacity m, so weight churn keeps the compiled shapes);
    None sizes each half to its edges exactly."""

    src_l: jax.Array
    dst_l: jax.Array
    w_l: jax.Array
    src_h: jax.Array
    dst_h: jax.Array
    w_h: jax.Array
    delta: int = _static()
    n: int = _static()
    canonical: bool = _static()
    capacity: Optional[int] = _static()

    @classmethod
    def build(cls, graph: COOGraph, cfg, capacity=None) -> "EdgeBackend":
        w = jnp.asarray(graph.w)
        if capacity is None:
            n_light = int(jnp.sum(w <= cfg.delta))   # fixes the shapes
            sizes = (n_light, graph.n_edges - n_light)
        else:
            sizes = (capacity, capacity)
        halves = _split_edges(jnp.asarray(graph.src), jnp.asarray(graph.dst),
                              w, delta=cfg.delta, n=graph.n_nodes,
                              n_light=sizes[0], n_heavy=sizes[1])
        return cls(*halves, cfg.delta, graph.n_nodes,
                   graph_is_canonical(graph), capacity)

    def edge_split(self) -> dict:
        """Edges in each half — what each phase's sweep relaxes; sentinel
        slots have ``src == n`` — and the slots both are padded to (None:
        each half holds exactly its edges)."""
        return {"light": int(jnp.sum(self.src_l < self.n)),
                "heavy": int(jnp.sum(self.src_h < self.n)),
                "capacity": self.capacity}

    @jax.named_scope("edge_sweep")
    def sweep(self, tent, mask, bucket_i, *, light: bool, packed: bool):
        src, dst, w = ((self.src_l, self.dst_l, self.w_l) if light
                       else (self.src_h, self.dst_h, self.w_h))
        tent = edge_sweep(tent, mask, src, dst, w,
                          delta=self.delta, light=light, packed=packed,
                          canonical=self.canonical)
        return tent, jnp.zeros((), bool)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class EllBackend(_FrontierCompactMixin, RelaxBackend):
    """Frontier-centric strategy: compacts the masked set into a
    fixed-capacity index buffer and expands light/heavy ELL rows
    (preprocessed split, paper Alg. 1 lines 3–5)."""

    light: ELLGraph
    heavy: ELLGraph
    delta: int = _static()
    n: int = _static()
    cap: int = _static()
    canonical: bool = _static()

    @classmethod
    def build(cls, graph: COOGraph, cfg, max_deg=None) -> "EllBackend":
        light, heavy = _ell_blocks(graph, cfg.delta, max_deg)
        return cls(light, heavy, cfg.delta, graph.n_nodes,
                   cfg.frontier_cap or graph.n_nodes,
                   graph_is_canonical(graph))

    @jax.named_scope("ell_sweep")
    def sweep(self, tent, mask, bucket_i, *, light: bool, packed: bool):
        fidx, over = self.compact(mask)
        ell = self.light if light else self.heavy
        tent = ell_sweep(tent, fidx, ell.nbr, ell.w, n=self.n, packed=packed,
                         canonical=self.canonical)
        return tent, over


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PallasEllBackend(_FrontierCompactMixin, _PallasScanMixin,
                       RelaxBackend):
    """ELL strategy with the hot loops on Pallas TPU kernels: candidate
    generation by ``kernels/ell_relax`` (scalar-prefetch row gather) and
    the three bucket scans fused by ``kernels/bucket_scan``. The
    scatter-min merge stays in XLA (C2), so packed (dist, pred) words
    still work — the kernel only ever sees int32 distances."""

    supports_vmap = False

    light: ELLGraph
    heavy: ELLGraph
    delta: int = _static()
    n: int = _static()
    cap: int = _static()
    kernel_path: str = _static()
    canonical: bool = _static()

    @classmethod
    def build(cls, graph: COOGraph, cfg, max_deg=None) -> "PallasEllBackend":
        path = kernel_path(cfg)
        light, heavy = _ell_blocks(graph, cfg.delta, max_deg)
        return cls(light, heavy, cfg.delta, graph.n_nodes,
                   cfg.frontier_cap or graph.n_nodes, path,
                   graph_is_canonical(graph))

    @jax.named_scope("ell_relax")
    def sweep(self, tent, mask, bucket_i, *, light: bool, packed: bool):
        fidx, over = self.compact(mask)
        ell = self.light if light else self.heavy
        d = dist_of(tent, packed)
        cand = ell_relax(fidx, d, ell.w, backend="pallas",
                         interpret=self.kernel_path == "interpret")
        rows_n = ell.nbr[fidx]
        src_ids = jnp.broadcast_to(fidx[:, None], rows_n.shape)
        if packed and self.canonical:
            # C4 on word order (the kernel only ever sees distances, so
            # INF candidates from padded slots are masked explicitly)
            word = packing.pack(cand, src_ids)
            word_dst = jnp.take(tent, rows_n, mode="fill",
                                fill_value=packing.INF_PACKED)
            ok = (cand < INF32) & (word < word_dst)
            words = jnp.where(ok, word, packing.INF_PACKED)
        else:
            d_dst = jnp.take(d, rows_n, mode="fill", fill_value=INF32)
            ok = cand < d_dst             # C4 filter on kernel candidates
            words = candidate_words(cand, src_ids, ok, packed)
        tent = tent.at[rows_n.ravel()].min(words.ravel(), mode="drop")
        return tent, over


def _lane_pad_block(nbr, w, sentinel: int):
    """Pad an ELL block's last (width) axis to whole 128-lane tiles with
    sentinel columns (neighbor ``sentinel``, weight INF) — the width the
    compiled ``frontier_relax`` row DMAs need. Done once at build so
    the kernel's wrapper never copies the block per call; the padded
    columns are invalid slots every consumer already ignores."""
    d = nbr.shape[-1]
    cols = [(0, 0)] * (nbr.ndim - 1) + [(0, lane_padded_width(d) - d)]
    return (jnp.pad(nbr, cols, constant_values=sentinel),
            jnp.pad(w, cols, constant_values=INF32))


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class FusedBackend(_FrontierCompactMixin, RelaxBackend):
    """Fused frontier strategy (DESIGN.md §12): the light phase runs the
    driver's *fused light phase* protocol — one
    ``kernels/frontier_relax`` step per inner iteration produces the
    compacted frontier, its gathered ELL rows, the any-reduce and the
    next-bucket min together, and the candidate words then flow through
    the shared ``ell_relax_words`` path into an XLA scatter-min (C2
    stays in XLA, so packed (cost, pred) words work unchanged). The
    heavy pass and any generic ``sweep`` call fall back to the plain
    compact-and-expand of ``EllBackend`` (the settled-set mask is not a
    bucket-membership scan, so there is nothing for the kernel to
    fuse)."""

    supports_fused_light = True

    light: ELLGraph
    heavy: ELLGraph
    delta: int = _static()
    n: int = _static()
    cap: int = _static()
    kernel_path: str = _static()
    width: int = _static()                # light ELL width before lane pad
    canonical: bool = _static()

    @property
    def supports_vmap(self):
        # the kernel path has no batching rule; the jnp twin vmaps fine
        return self.kernel_path == "twin"

    @classmethod
    def build(cls, graph: COOGraph, cfg, max_deg=None) -> "FusedBackend":
        path = kernel_path(cfg)
        light, heavy = _ell_blocks(graph, cfg.delta, max_deg)
        width = light.max_deg
        if path == "compiled":
            nbr, w = _lane_pad_block(light.nbr, light.w, graph.n_nodes)
            light = ELLGraph(nbr, w, light.n_nodes, nbr.shape[1])
        return cls(light, heavy, cfg.delta, graph.n_nodes,
                   cfg.frontier_cap or graph.n_nodes, path, width,
                   graph_is_canonical(graph))

    @jax.named_scope("frontier_relax")
    def _fused_step(self, dist, explored, bucket_i):
        ell = self.light
        fidx, rows_n, rows_w, count, any_, nxt = frontier_relax(
            dist, explored, bucket_i, ell.nbr, ell.w, delta=self.delta,
            cap=self.cap, base=0, sent=self.n,
            backend="ref" if self.kernel_path == "twin" else "pallas",
            interpret=self.kernel_path == "interpret")
        return (fidx, rows_n[:, :self.width], rows_w[:, :self.width], count,
                any_, nxt)

    def fused_iter(self, tent, explored, in_s, bucket_i, *, packed: bool):
        """One whole light inner iteration: kernel step (scan + compact
        + gather), settled-set bookkeeping, shared-path relaxation.
        Replays the classic loop's op sequence on the same states —
        explored/S updates read the *pre*-relaxation distances — so
        state trajectories are bitwise those of ``edge``/``ell``
        (DESIGN.md §12). An empty frontier makes every phase a sentinel
        no-op, which is what lets the driver run this unconditionally."""
        d = dist_of(tent, packed)
        fidx, rows_n, rows_w, count, any_, _ = self._fused_step(
            d, explored, bucket_i)
        d_f = jnp.take(d, fidx, mode="fill", fill_value=INF32)
        explored = explored.at[fidx].set(d_f, mode="drop")
        in_s = in_s.at[fidx].set(True, mode="drop")
        words = ell_relax_words(tent, fidx, rows_n, rows_w, n=self.n,
                                packed=packed, canonical=self.canonical)
        tent = tent.at[rows_n.ravel()].min(words.ravel(), mode="drop")
        return tent, explored, in_s, any_, count > self.cap

    def fused_next(self, dist, explored, bucket_i):
        """Next-bucket min for the driver's bucket advance — the
        kernel's scalar output (the fused replacement of the post-heavy
        ``scan_bucket`` call; bitwise equal, same formulas)."""
        if self.kernel_path != "twin":
            return self._fused_step(dist, explored, bucket_i)[5]
        return scan_bucket(dist, explored, bucket_i, delta=self.delta)[2]

    @jax.named_scope("ell_sweep")
    def sweep(self, tent, mask, bucket_i, *, light: bool, packed: bool):
        fidx, over = self.compact(mask)
        ell = self.light if light else self.heavy
        tent = ell_sweep(tent, fidx, ell.nbr, ell.w, n=self.n, packed=packed,
                         canonical=self.canonical)
        return tent, over


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class GridPallasBackend(_PallasScanMixin, RelaxBackend):
    """Game-map strategy (paper §4 'Game Maps'): the graph is an
    occupancy grid, so relaxation is the ``kernels/grid_relax`` masked
    min-plus stencil — no adjacency materialization at all. The stencil
    recomputes bucket membership from ``tent`` in-kernel, so the driver's
    mask argument is advisory; re-relaxing settled cells is idempotent
    (the paper's redundant-work trade). int32 distances only
    (``pred_mode='packed'`` is rejected at build time)."""

    supports_vmap = False

    free: jax.Array                       # bool[H, W] occupancy mask
    delta: int = _static()
    shape: Tuple[int, int] = _static()
    costs: Tuple[int, int] = _static()    # (straight, diagonal)
    kernel_path: str = _static()

    @classmethod
    def build(cls, graph: COOGraph, cfg, free_mask) -> "GridPallasBackend":
        path = kernel_path(cfg)
        free = jnp.asarray(free_mask, bool)
        if free.ndim != 2 or free.size != graph.n_nodes:
            raise ValueError(
                f"free_mask shape {free.shape} does not cover the "
                f"{graph.n_nodes}-vertex graph")
        return cls(free, cfg.delta, tuple(free.shape),
                   tuple(cfg.grid_costs), path)

    @jax.named_scope("grid_relax")
    def sweep(self, tent, mask, bucket_i, *, light: bool, packed: bool):
        h, w = self.shape
        out = grid_relax(tent.reshape(h, w), self.free, bucket_i,
                         delta=self.delta, cost_straight=self.costs[0],
                         cost_diag=self.costs[1], light=light,
                         backend="pallas",
                         interpret=self.kernel_path == "interpret")
        return out.reshape(-1), jnp.zeros((), bool)


# ---------------------------------------------------------------------------
# mesh-sharded backends (DESIGN.md §9)
# ---------------------------------------------------------------------------

_SHARD_AXIS = "shard"


def resolve_n_shards(n_shards) -> int:
    """Concrete shard count for a config's ``n_shards`` (None = every
    local device). Bounded by the device count: ``shard_map`` needs one
    device per mesh slot."""
    ndev = jax.device_count()
    if n_shards is None:
        return ndev
    if not 1 <= n_shards <= ndev:
        raise ValueError(
            f"n_shards={n_shards} needs 1..{ndev} devices (run under "
            "XLA_FLAGS=--xla_force_host_platform_device_count=K to fake "
            "a K-device host mesh)")
    return int(n_shards)


def _shard_mesh(n_shards: int):
    return compat.make_mesh((n_shards,), (_SHARD_AXIS,))


def _place_shards(blocks, n_shards: int):
    """Put stacked per-shard blocks (leading axis = shard; a pytree of
    them is fine) one block per mesh device at build time. Left
    uncommitted they would sit on device 0 and be re-sent to every
    shard on each call."""
    spec = jax.sharding.NamedSharding(_shard_mesh(n_shards),
                                      PartitionSpec(_SHARD_AXIS))
    return jax.device_put(blocks, spec)


def _inf_word(packed: bool):
    return jnp.asarray(packing.INF_PACKED, jnp.int64) if packed \
        else jnp.asarray(INF32, jnp.int32)


class _ShardedMixin:
    """Shared shard_map plumbing: a 1-D mesh over ``n_shards`` devices,
    built at trace time (meshes are host objects, not pytree leaves).
    Consumers declare the static field ``n_shards``."""

    def _mesh(self):
        return _shard_mesh(self.n_shards)

    def _shard_map(self, body, n_sharded_args, n_outs):
        spec = PartitionSpec(_SHARD_AXIS)
        rep = PartitionSpec()
        return compat.shard_map(
            body, mesh=self._mesh(),
            in_specs=(rep, rep) + (spec,) * n_sharded_args,
            out_specs=(rep,) * n_outs if n_outs > 1 else rep,
            check_vma=False)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ShardedEdgeBackend(_ShardedMixin, RelaxBackend):
    """Edge-centric strategy over an SPMD mesh: every device sweeps its
    own edge shard (``graphs.partition.partition_edges`` row ownership)
    into a full-width candidate buffer, then the buffers are merged with
    an all-reduce min and folded into the replicated tent. Min is
    associative and commutative on the tent words — int32 distances or
    packed int64 (cost, pred) — so the result is bitwise identical to
    the single-device ``edge`` backend for any shard count: the paper's
    CAS loop (C2) becomes a deterministic collective (DESIGN.md §9)."""

    src: jax.Array                        # int32[n_shards, E_pad]
    dst: jax.Array
    w: jax.Array
    delta: int = _static()
    n: int = _static()
    n_shards: int = _static()
    canonical: bool = _static()

    @classmethod
    def build(cls, graph: COOGraph, cfg) -> "ShardedEdgeBackend":
        shards = resolve_n_shards(cfg.n_shards)
        part = partition_edges(graph, shards)
        src, dst, w = _place_shards((part.src, part.dst, part.w), shards)
        return cls(src, dst, w, cfg.delta, graph.n_nodes, shards,
                   graph_is_canonical(graph))

    @jax.named_scope("sharded_edge_sweep")
    def sweep(self, tent, mask, bucket_i, *, light: bool, packed: bool):
        delta, n, canonical = self.delta, self.n, self.canonical

        def body(tent_r, mask_r, src, dst, w):
            src, dst, w = src[0], dst[0], w[0]    # shed the shard dim
            words = edge_relax_words(tent_r, mask_r,
                                     src, dst, w, delta=delta, light=light,
                                     packed=packed, canonical=canonical)
            buf = jnp.full((n,), _inf_word(packed)).at[dst].min(
                words, mode="drop")
            return jnp.minimum(tent_r, lax.pmin(buf, _SHARD_AXIS))

        tent = self._shard_map(body, 3, 1)(tent, mask, self.src, self.dst,
                                           self.w)
        return tent, jnp.zeros((), bool)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ShardedEllBackend(_ShardedMixin, RelaxBackend):
    """Frontier-centric strategy over an SPMD mesh: each device compacts
    the frontier slice of its owned vertex range and expands its local
    light/heavy ELL row block (``graphs.partition.partition_ell``), then
    candidates merge with the same all-reduce min word schedule as
    ``sharded_edge``. ``cap`` is the *per-shard* compaction capacity
    (default: the full owned range, which cannot overflow); the overflow
    flag is any-reduced across shards."""

    part: ELLPartition
    delta: int = _static()
    n: int = _static()
    n_shards: int = _static()
    cap: int = _static()
    canonical: bool = _static()

    @classmethod
    def build(cls, graph: COOGraph, cfg) -> "ShardedEllBackend":
        shards = resolve_n_shards(cfg.n_shards)
        part = _place_shards(partition_ell(graph, shards, cfg.delta), shards)
        cap = min(cfg.frontier_cap or part.shard_nodes, part.shard_nodes)
        return cls(part, cfg.delta, graph.n_nodes, shards, cap,
                   graph_is_canonical(graph))

    @jax.named_scope("sharded_ell_sweep")
    def sweep(self, tent, mask, bucket_i, *, light: bool, packed: bool):
        part = self.part
        nbr = part.light_nbr if light else part.heavy_nbr
        w_ell = part.light_w if light else part.heavy_w
        n, s_nodes, cap = self.n, part.shard_nodes, self.cap
        canonical = self.canonical
        n_pad = self.n_shards * s_nodes

        def body(tent_r, mask_r, nbr_s, w_s):
            nbr_s, w_s = nbr_s[0], w_s[0]         # (S + 1, D)
            base = lax.axis_index(_SHARD_AXIS) * s_nodes
            maskp = jnp.pad(mask_r, (0, n_pad - n))
            local = lax.dynamic_slice_in_dim(maskp, base, s_nodes)
            lidx = jnp.nonzero(local, size=cap,
                               fill_value=s_nodes)[0].astype(jnp.int32)
            over = local.sum() > cap
            # global ids of the compacted rows; sentinel slots gather INF
            gidx = jnp.where(lidx < s_nodes, lidx + base, n).astype(jnp.int32)
            rows_n = nbr_s[lidx]                  # (cap, D), global ids
            rows_w = w_s[lidx]
            words = ell_relax_words(tent_r, gidx,
                                    rows_n, rows_w, n=n, packed=packed,
                                    canonical=canonical)
            buf = jnp.full((n,), _inf_word(packed)).at[rows_n.ravel()].min(
                words.ravel(), mode="drop")
            tent_out = jnp.minimum(tent_r, lax.pmin(buf, _SHARD_AXIS))
            over_all = lax.pmax(over.astype(jnp.int32), _SHARD_AXIS) > 0
            return tent_out, over_all

        return self._shard_map(body, 2, 2)(tent, mask, nbr, w_ell)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ShardedFusedBackend(ShardedEllBackend):
    """Fused frontier strategy over an SPMD mesh: each device runs the
    ``kernels/frontier_relax`` step on its *owned* vertex slice (scan +
    compact + local ELL row gather with global neighbor ids), then the
    iteration's three state updates merge with deterministic collectives
    — tent words through the same all-reduce min as every sharded
    backend (DESIGN.md §9), ``explored`` through ``pmin`` (each shard
    lowers only its owned frontier entries, and a frontier member's
    tent is strictly below its explored mark, so the element-wise min
    over per-shard copies IS the sequential update), and the settled
    mask / any / overflow flags through ``pmax``. Ownership is disjoint
    and min/max are associative-commutative, so the result is bitwise
    the single-device ``fused`` iteration for any shard count."""

    supports_fused_light = True

    kernel_path: str = _static()
    width: int = _static()                # light ELL width before lane pad

    @property
    def supports_vmap(self):
        return self.kernel_path == "twin"

    @classmethod
    def build(cls, graph: COOGraph, cfg) -> "ShardedFusedBackend":
        path = kernel_path(cfg)
        shards = resolve_n_shards(cfg.n_shards)
        part = partition_ell(graph, shards, cfg.delta)
        width = part.light_deg
        if path == "compiled":
            nbr, w = _lane_pad_block(part.light_nbr, part.light_w,
                                     graph.n_nodes)
            part = dataclasses.replace(part, light_nbr=nbr, light_w=w,
                                       light_deg=nbr.shape[-1])
        part = _place_shards(part, shards)
        cap = min(cfg.frontier_cap or part.shard_nodes, part.shard_nodes)
        return cls(part, cfg.delta, graph.n_nodes, shards, cap,
                   graph_is_canonical(graph), path, width)

    @jax.named_scope("frontier_relax")
    def fused_iter(self, tent, explored, in_s, bucket_i, *, packed: bool):
        part = self.part
        n, s_nodes, cap = self.n, part.shard_nodes, self.cap
        delta, canonical, width = self.delta, self.canonical, self.width
        path = self.kernel_path
        n_pad = self.n_shards * s_nodes

        def body(tent_r, explored_r, in_s_r, i_r, nbr_s, w_s):
            nbr_s, w_s = nbr_s[0], w_s[0]         # (S + 1, D)
            base = lax.axis_index(_SHARD_AXIS) * s_nodes
            d = dist_of(tent_r, packed)
            dp = jnp.pad(d, (0, n_pad - n), constant_values=INF32)
            ep = jnp.pad(explored_r, (0, n_pad - n), constant_values=INF32)
            d_loc = lax.dynamic_slice_in_dim(dp, base, s_nodes)
            e_loc = lax.dynamic_slice_in_dim(ep, base, s_nodes)
            fidx, rows_n, rows_w, count, any_l, _ = frontier_relax(
                d_loc, e_loc, i_r, nbr_s, w_s, delta=delta, cap=cap,
                base=base, sent=n,
                backend="ref" if path == "twin" else "pallas",
                interpret=path == "interpret")
            rows_n, rows_w = rows_n[:, :width], rows_w[:, :width]
            d_f = jnp.take(d, fidx, mode="fill", fill_value=INF32)
            explored_out = lax.pmin(
                explored_r.at[fidx].set(d_f, mode="drop"), _SHARD_AXIS)
            in_s_out = lax.pmax(
                in_s_r.at[fidx].set(True, mode="drop").astype(jnp.int32),
                _SHARD_AXIS) > 0
            words = ell_relax_words(tent_r, fidx, rows_n, rows_w, n=n,
                                    packed=packed, canonical=canonical)
            buf = jnp.full((n,), _inf_word(packed)).at[rows_n.ravel()].min(
                words.ravel(), mode="drop")
            tent_out = jnp.minimum(tent_r, lax.pmin(buf, _SHARD_AXIS))
            any_all = lax.pmax(any_l.astype(jnp.int32), _SHARD_AXIS) > 0
            over = (count > cap).astype(jnp.int32)
            over_all = lax.pmax(over, _SHARD_AXIS) > 0
            return tent_out, explored_out, in_s_out, any_all, over_all

        rep, spec = PartitionSpec(), PartitionSpec(_SHARD_AXIS)
        fn = compat.shard_map(
            body, mesh=self._mesh(),
            in_specs=(rep, rep, rep, rep, spec, spec),
            out_specs=(rep, rep, rep, rep, rep),
            check_vma=False)       # no replication rule for pallas_call
        return fn(tent, explored, in_s, jnp.asarray(bucket_i, jnp.int32),
                  part.light_nbr, part.light_w)

    def fused_next(self, dist, explored, bucket_i):
        """Replicated next-bucket min: the post-heavy scan runs on the
        already-merged tent, so the plain jnp scan is both cheapest and
        trivially bitwise (same formulas as the kernel output)."""
        return scan_bucket(dist, explored, bucket_i, delta=self.delta)[2]


def make_backend(graph: COOGraph, cfg, free_mask=None) -> RelaxBackend:
    """Route a (graph, config) pair to its backend. ``free_mask`` marks
    the game-map graph class: under ``strategy='pallas'`` it selects the
    grid-stencil kernel instead of the ELL kernels. ``cfg="auto"``
    consults the tuning subsystem (estimator + cache, DESIGN.md §7)."""
    if isinstance(cfg, str):
        from repro.tune import resolve_config   # lazy: tune imports core
        if cfg != "auto":
            raise ValueError(f"unknown config string {cfg!r}")
        cfg = resolve_config(graph, free_mask=free_mask, sources=None)
    if cfg.strategy == "edge":
        return EdgeBackend.build(graph, cfg)
    if cfg.strategy == "ell":
        return EllBackend.build(graph, cfg)
    if cfg.strategy == "fused":
        return FusedBackend.build(graph, cfg)
    if cfg.strategy == "sharded_edge":
        return ShardedEdgeBackend.build(graph, cfg)
    if cfg.strategy == "sharded_ell":
        return ShardedEllBackend.build(graph, cfg)
    if cfg.strategy == "sharded_fused":
        return ShardedFusedBackend.build(graph, cfg)
    assert cfg.strategy == "pallas", cfg.strategy
    if free_mask is not None:
        if cfg.pred_mode == "packed":
            raise ValueError(
                "grid-stencil pallas backend carries int32 distances only; "
                "use pred_mode='argmin' (post-hoc tree recovery)")
        return GridPallasBackend.build(graph, cfg, free_mask)
    return PallasEllBackend.build(graph, cfg)
