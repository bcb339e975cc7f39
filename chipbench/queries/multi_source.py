"""Many-source analytics: ``sources_per_request`` sources per request,
drawn by ``source_draw``, answered with one distance row and one
predecessor row per source (the program's ``MultiSource``). A request
is complete when both rows are ready on the device.

The check covers every answered request: each lane's (distance,
predecessor) pair has to be a shortest-path tree (exact on its own),
and ``check_lanes`` lanes, drawn by the run's seed from all answered
lanes, have to equal Dijkstra's rows entry for entry."""
import dataclasses
import functools

import numpy as np

from chipbench import reference
from chipbench.plugins import load


def draw(rng, mix, sources, targets):
    pick = load("draws", mix.get("source_draw", "uniform")).draw
    k = int(mix["sources_per_request"])
    return tuple(int(s) for s in pick(rng, sources, size=k)), -1


def solves(mix) -> int:
    return int(mix["sources_per_request"])


def warm(mix, source: int, lane_width: int):
    """One request of the cell's width from a source without edges."""
    return [((source,) * solves(mix), source)]


def program_query(sources, target):
    from repro.api import MultiSource

    return MultiSource(list(sources))


def ready(res) -> None:
    import jax

    jax.block_until_ready((res.dist, res.pred))


def check(hg, mix, answered, rng) -> dict:
    tree, lanes = 0, []
    for i, r in enumerate(answered):
        d = np.asarray(r.result.dist)
        p = np.asarray(r.result.pred)
        for j, s in enumerate(r.sources):
            tree += reference.tree_faults(hg, s, d[j], p[j])
            lanes.append((i, j))
    k = min(int(mix["check_lanes"]), len(lanes))
    pick = [lanes[x] for x in sorted(rng.choice(len(lanes), size=k,
                                                replace=False))]
    dist = reference.dist_mismatches(
        hg, [answered[i].sources[j] for i, j in pick],
        np.stack([np.asarray(answered[i].result.dist[j]) for i, j in pick])
        if pick else np.zeros((0, hg.n), np.int64))
    return {"lanes_checked": (len(lanes), None),
            "tree_faults": (tree, 0),
            "dist_mismatches": (dist, 0)}


def narrowed(hg, answered, dtype: str):
    """The answers with each lane's distances replaced by the
    reference's in ``dtype`` (the control of the check), the
    predecessors as served; and how many distances the narrow type
    changed."""
    out, changed = [], 0
    for r in answered:
        exact = _exact(hg, tuple(r.sources))
        ref = reference.narrow(exact, dtype)
        changed += int((ref != exact).sum())
        res = dataclasses.replace(r.result, dist=ref.astype(np.int32))
        out.append(dataclasses.replace(r, result=res))
    return out, changed


@functools.lru_cache(maxsize=16)
def _exact(hg, sources):
    return hg.dijkstra(sources)
