"""The plain reference that decides ``correct``: the graph on the host
with duplicate edges reduced to their minimum weight, scipy's Dijkstra
over it, and checks of what a client received against it.

Nothing here imports the program or takes anything it made: the edge
arrays come from the benchmark's own generator (``graphs.py``).

Each check returns a count of faults; the run compares every count with
its limit (0: the engine promises exact int32 distances).
"""
from __future__ import annotations

import numpy as np

INF32 = 2**31 - 1


class HostGraph:
    """The graph on the host with duplicate edges reduced to their
    minimum weight: the weight lookup for path/tree checks and the CSR
    matrix for scipy's Dijkstra. Edges whose ends lie outside
    ``[0, n)`` (sentinel padding) are dropped."""

    def __init__(self, src, dst, w, n: int):
        src = np.asarray(src).astype(np.int64)
        dst = np.asarray(dst).astype(np.int64)
        w = np.asarray(w).astype(np.int64)
        real = (src >= 0) & (src < n) & (dst >= 0) & (dst < n)
        src, dst, w = src[real], dst[real], w[real]
        key = src * n + dst
        order = np.lexsort((w, key))          # by key, then weight
        key = key[order]
        first = np.ones(key.shape[0], bool)
        first[1:] = key[1:] != key[:-1]
        self.n = n
        self.key = key[first]
        self.w = w[order][first]
        self.src = self.key // n
        self.dst = self.key % n
        self._csr = None

    def weight(self, u, v):
        """Min weight of edges u->v (arrays), -1 where there is none."""
        k = np.asarray(u, np.int64) * self.n + np.asarray(v, np.int64)
        if self.key.shape[0] == 0:
            return np.full(k.shape, -1, np.int64)
        i = np.clip(np.searchsorted(self.key, k), 0, self.key.shape[0] - 1)
        return np.where(self.key[i] == k, self.w[i], -1)

    def dijkstra(self, sources, limit=np.inf):
        """int64 distances, INF32 where unreachable (or beyond
        ``limit``), one row per source."""
        import scipy.sparse
        import scipy.sparse.csgraph

        if self._csr is None:
            self._csr = scipy.sparse.csr_matrix(
                (self.w.astype(np.float64), (self.src, self.dst)),
                shape=(self.n, self.n))
        d = scipy.sparse.csgraph.dijkstra(self._csr, directed=True,
                                          indices=list(sources),
                                          limit=limit)
        return np.where(np.isinf(d), INF32, d).astype(np.int64)


def tree_faults(hg: HostGraph, source: int, dist, pred) -> int:
    """Vertices at which ``(dist, pred)`` is not a shortest-path tree
    from ``source``: a wrong source entry, a reached vertex whose
    predecessor edge is missing or not tight, an unreached vertex with
    a predecessor, and each edge that could still shorten its head
    (with tight tree edges of weight >= 1 the last test makes ``dist``
    exact, so this check alone needs no Dijkstra)."""
    dist = np.asarray(dist, np.int64)
    pred = np.asarray(pred, np.int64)
    n = hg.n
    faults = int(dist[source] != 0) + int(pred[source] != -1)
    reached = dist < INF32
    reached[source] = False
    v = np.flatnonzero(reached)
    p = pred[v]
    inrange = (p >= 0) & (p < n)
    w = np.full(v.shape, -1, np.int64)
    w[inrange] = hg.weight(p[inrange], v[inrange])
    ok = inrange & (w >= 0)
    ok[ok] = dist[p[ok]] + w[ok] == dist[v[ok]]
    faults += int((~ok).sum())
    others = ~reached
    others[source] = False
    faults += int((pred[others] != -1).sum())
    d_src = dist[hg.src]
    live = d_src < INF32
    faults += int((d_src[live] + hg.w[live] < dist[hg.dst[live]]).sum())
    return faults


def path_fault(hg: HostGraph, source: int, target: int, distance: int,
               path) -> bool:
    """True when a point-to-point answer is not a walk from ``source``
    to ``target`` whose edge weights add up to ``distance`` (or, for an
    unreachable target, when a path came with it)."""
    if distance >= INF32:
        return path is not None
    if path is None or len(path) == 0:
        return True
    path = np.asarray(path, np.int64)
    if path[0] != source or path[-1] != target:
        return True
    w = hg.weight(path[:-1], path[1:])
    return bool((w < 0).any() or int(w.sum()) != distance)


def dist_mismatches(hg: HostGraph, sources, dist_rows) -> int:
    """Entries of served distance rows that differ from Dijkstra."""
    ref = hg.dijkstra(sources)
    got = np.asarray(dist_rows, np.int64).reshape(ref.shape)
    return int((got != ref).sum())


def narrow(dist, dtype: str):
    """Distances as a reference that holds them in the narrower
    ``dtype`` would serve them: ``bfloat16`` and ``float16`` round to
    the nearest value the type holds, ``int16`` is exact up to its
    largest value, which stands for unreachable. INF32 where the narrow
    type has no finite value, and where the distance is INF32."""
    d = np.asarray(dist, np.int64)
    if dtype == "int16":
        out = np.where(d >= np.iinfo(np.int16).max, INF32, d)
    elif dtype in ("bfloat16", "float16"):
        import ml_dtypes

        t = ml_dtypes.bfloat16 if dtype == "bfloat16" else np.float16
        f = d.astype(np.float32).astype(t).astype(np.float64)
        out = np.where(np.isfinite(f), f, INF32).astype(np.int64)
    else:
        raise ValueError(f"no narrow type {dtype!r}")
    return np.where(d >= INF32, INF32, out)


def p2p_mismatches(hg: HostGraph, answers) -> int:
    """Point-to-point answers ``(source, target, distance)`` whose
    distance is not the shortest. Each source's Dijkstra stops at the
    served distance, which it has to reach exactly: a shorter path shows
    as a smaller distance, a served distance below the truth as an
    unreached target."""
    bad = 0
    for s, t, d in answers:
        if d < 0:
            bad += 1
            continue
        limit = np.inf if d >= INF32 else float(d)
        ref = int(hg.dijkstra([s], limit=limit)[0, t])
        bad += int(ref != d)
    return bad
