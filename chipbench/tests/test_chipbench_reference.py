"""The reference check catches what it must: a corrupted distance, a
wrong predecessor, a broken path."""
import numpy as np
import pytest

from chipbench import reference

INF = reference.INF32


@pytest.fixture
def graph():
    # 0 -> 1 (2), 1 -> 2 (3), 0 -> 2 (7), 2 -> 3 (1), duplicate 0 -> 1 (5)
    src = np.array([0, 1, 0, 2, 0])
    dst = np.array([1, 2, 2, 3, 1])
    w = np.array([2, 3, 7, 1, 5])
    return reference.HostGraph(src, dst, w, 5)


DIST = np.array([0, 2, 5, 6, INF])
PRED = np.array([-1, 0, 1, 2, -1])


def test_exact_tree_passes(graph):
    assert reference.tree_faults(graph, 0, DIST, PRED) == 0
    assert reference.dist_mismatches(graph, [0], DIST[None]) == 0
    assert (graph.dijkstra([0]) == DIST).all()


@pytest.mark.parametrize("vertex,delta", [(2, 1), (3, -1), (1, 1)])
def test_corrupted_distance_is_caught(graph, vertex, delta):
    d = DIST.copy()
    d[vertex] += delta
    assert reference.tree_faults(graph, 0, d, PRED) > 0
    assert reference.dist_mismatches(graph, [0], d[None]) == 1


def test_wrong_predecessor_is_caught(graph):
    p = PRED.copy()
    p[2] = 0                                   # 0 -> 2 exists but is not tight
    assert reference.tree_faults(graph, 0, DIST, p) == 1
    p = PRED.copy()
    p[4] = 3                                   # unreached vertex with a pred
    assert reference.tree_faults(graph, 0, DIST, p) == 1


def test_consistent_but_too_long_distances_are_caught(graph):
    # a tight tree over the long 0 -> 2 edge: only the edge check sees it
    d = np.array([0, 2, 7, 8, INF])
    p = np.array([-1, 0, 0, 2, -1])
    assert reference.tree_faults(graph, 0, d, p) == 1


@pytest.mark.parametrize("path,distance,bad", [
    ([0, 1, 2, 3], 6, False),
    ([0, 2, 3], 8, False),                      # a walk: not shortest
    ([0, 1, 3], 6, True),                       # no edge 1 -> 3
    ([0, 1, 2], 6, True),                       # wrong end
    ([0, 1, 2, 3], 7, True),                    # weights add up to 6
    (None, 6, True),
])
def test_broken_path_is_caught(graph, path, distance, bad):
    assert reference.path_fault(graph, 0, 3, distance, path) is bad


def test_p2p_mismatch_catches_a_longer_walk(graph):
    assert reference.p2p_mismatches(graph, [(0, 3, 6), (0, 4, INF)]) == 0
    assert reference.p2p_mismatches(graph, [(0, 3, 8)]) == 1
    assert reference.p2p_mismatches(graph, [(0, 3, 5), (0, 3, -1)]) == 2


def test_sentinel_padding_edges_are_ignored():
    hg = reference.HostGraph(np.array([0, 3]), np.array([1, 3]),
                             np.array([4, 0]), 3)
    assert hg.key.tolist() == [1]
    assert (hg.dijkstra([0])[0] == [0, 4, INF]).all()
