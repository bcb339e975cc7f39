"""Point-to-point paths: a source drawn by ``source_draw`` and a target
by ``target_draw``, answered with the distance and the path on the
host (the program's ``PointToPoint``); the ticket resolves when both
are there.

The check covers every answered request's path (a walk from the source
to the target whose edge weights add up to the served distance), and
compares with Dijkstra the distances of ``check_requests`` answers
drawn by the run's seed and of the longest answer."""
import dataclasses
import functools

import numpy as np

from chipbench import reference
from chipbench.plugins import load


def draw(rng, mix, sources, targets):
    src = load("draws", mix.get("source_draw", "uniform")).draw
    tgt = load("draws", mix.get("target_draw", "uniform")).draw
    return (int(src(rng, sources)),), int(tgt(rng, targets))


def solves(mix) -> int:
    return 1


def warm(mix, source: int, lane_width: int):
    """A whole lane batch, so that every lane's row copy is compiled."""
    return [((source,), source)] * lane_width


def program_query(sources, target):
    from repro.api import PointToPoint

    return PointToPoint(sources[0], target)


def ready(res) -> None:
    """The answer is on the host once the ticket resolves."""


def check(hg, mix, answered, rng) -> dict:
    paths = sum(reference.path_fault(hg, r.sources[0], r.target,
                                     r.result.distance, r.result.path)
                for r in answered)
    k = min(int(mix["check_requests"]), len(answered))
    pick = set(rng.choice(len(answered), size=k, replace=False).tolist()
               if k else [])
    if answered:
        pick.add(max(range(len(answered)),
                     key=lambda j: (answered[j].result.distance
                                    < reference.INF32,
                                    answered[j].result.distance)))
    sample = [(answered[j].sources[0], answered[j].target,
               answered[j].result.distance) for j in sorted(pick)]
    return {"answers_checked": (len(answered), None),
            "path_faults": (paths, 0),
            "dist_mismatches": (reference.p2p_mismatches(hg, sample), 0)}


def narrowed(hg, answered, dtype: str):
    """The answers with each distance replaced by the reference's in
    ``dtype`` (the control of the check), the paths as served; and how
    many distances the narrow type changed. Each search stops at the
    served distance where it reaches the target there, and runs on
    where it does not."""
    out, changed = [], 0
    for r in answered:
        ref = _exact(hg, r.sources[0], r.target, r.result.distance)
        d = int(reference.narrow(ref, dtype))
        changed += int(d != ref)
        res = dataclasses.replace(r.result, distance=d)
        out.append(dataclasses.replace(r, result=res))
    return out, changed


@functools.lru_cache(maxsize=4096)
def _exact(hg, s, t, bound):
    ref = int(hg.dijkstra([s], limit=np.inf if bound >= reference.INF32
                          else float(bound))[0, t])
    return ref if ref < reference.INF32 else int(hg.dijkstra([s])[0, t])
