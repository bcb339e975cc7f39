"""The one traffic generator. A traffic mix is a JSON file of parameters
under ``traffic/``; this module turns it and a deployment into the
requests a run sends. The parts a mix names are files of their own:

* ``query``: the query kind, ``queries/<query>.py``. It draws a
  request's vertices, makes the program's query, waits for its answer
  and checks the answers against the reference.
* ``source_draw`` / ``target_draw`` (default ``"uniform"``): how the
  query kind picks vertices from the deployment's sets,
  ``draws/<name>.py``.
* ``loop``: ``"closed"`` (a client that keeps ``in_flight`` requests
  outstanding and sends the next when one completes) or ``"open"``
  (requests due on a schedule drawn by ``arrivals/<arrivals>.py``, sent
  whether or not earlier ones came back).
* ``pool`` and ``pool_seed``: the requests are a pool of ``pool``
  drawn from ``pool_seed``, sent in a shuffled order and cycled if a
  run needs more.

Every draw comes from ``pool_seed`` too, so every run of a mix sends
the same requests at the same times: the order of arrivals moves a
queue's tail by far more than two runs of one order differ, so a run's
seed draws only which answers are compared with the reference. The
program sees only the queries.
"""
from __future__ import annotations

import dataclasses
from typing import List

import numpy as np

from chipbench.plugins import load


@dataclasses.dataclass(frozen=True)
class Request:
    index: int
    due: float            # seconds after the window opens (open loop)
    sources: tuple        # one source for point-to-point
    target: int = -1


def _rng(seed: int, purpose: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), purpose]))


def query_kind(mix: dict):
    return load("queries", mix["query"])


def pool(mix: dict, sources: np.ndarray, targets: np.ndarray) -> list:
    """The mix's pool of ``(sources, target)`` requests."""
    rng = _rng(int(mix["pool_seed"]), 3)
    kind = query_kind(mix)
    return [kind.draw(rng, mix, sources, targets)
            for _ in range(int(mix["pool"]))]


def requests(mix: dict, sources: np.ndarray, targets: np.ndarray,
             seconds: float, count: int) -> List[Request]:
    """The run's requests: for an open loop those due in the window,
    for a closed loop the first ``count`` the client may send."""
    seed = int(mix["pool_seed"])
    if mix["loop"] == "open":
        dues = load("arrivals", mix["arrivals"]).dues(mix, seconds, seed)
    elif mix["loop"] == "closed":
        dues = [0.0] * count
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    work = pool(mix, sources, targets)
    order = _rng(seed, 6).permutation(len(work))
    return [Request(i, due, *work[order[i % len(work)]])
            for i, due in enumerate(dues)]
