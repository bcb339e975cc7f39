"""Fused frontier kernel (DESIGN.md §12): the single-pallas_call
scan + on-chip compaction + ELL row gather, its jnp oracle twin, and the
``fused`` / ``sharded_fused`` strategies built on it.

Layers under test, bottom up:

* kernel vs oracle — ``frontier_relax(backend="pallas", interpret=True)``
  bitwise-equals ``backend="ref"`` on random instances, including the
  exact-fill tile (population == cap) and overflow (population > cap);
* engine — ``fused`` bitwise-equals ``edge`` (dist, pred, both
  iteration counters: the atomic-iteration loop must not change the
  schedule), with the crafted exact-fill frontier raising no overflow
  and the all-heavy (zero-width light block) graph routing through the
  D == 0 oracle path;
* façade — a scratch-capacity overflow routes through the one fallback
  point, ``Plan.solve(fallback=True)``: demotion, never a wrong answer;
* warm repair — a fused plan's weight-update resolve stays bitwise
  cold-identical (the capped repair twin is itself fused);
* mesh — ``sharded_fused`` on a real 8-device host mesh reproduces the
  single-device ``fused`` engine bitwise (subprocess: the forced device
  count must be set before JAX initializes).
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.api import Engine, SingleSource
from repro.compat import enable_x64
from repro.core import DeltaConfig, DeltaSteppingSolver, dijkstra
from repro.graphs import watts_strogatz
from repro.graphs.structures import COOGraph, INF32
from repro.kernels.frontier_relax import frontier_relax, frontier_relax_ref


def _solve(g, src, strategy, **kw):
    kw.setdefault("delta", 10)
    return DeltaSteppingSolver(g, DeltaConfig(strategy=strategy, **kw)
                               ).solve(src)


def _assert_bitwise(a, b, tag):
    np.testing.assert_array_equal(
        np.asarray(a.dist), np.asarray(b.dist), err_msg=f"{tag}: dist")
    np.testing.assert_array_equal(
        np.asarray(a.pred), np.asarray(b.pred), err_msg=f"{tag}: pred")
    assert int(a.outer_iters) == int(b.outer_iters), tag
    assert int(a.inner_iters) == int(b.inner_iters), tag


# ---------------------------------------------------------------------------
# kernel vs oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", range(6))
def test_kernel_matches_oracle_random(seed):
    """Bitwise kernel/oracle parity on random (dist, explored, ELL)
    instances; caps drawn to cover under-fill, exact-fill and overflow
    of the compaction scratch."""
    rng = np.random.default_rng(seed)
    s = int(rng.integers(2, 400))
    d_w = int(rng.integers(1, 5))
    delta = int(rng.integers(1, 30))
    base = int(rng.integers(0, 3)) * s
    dist = np.where(rng.random(s) < 0.3, int(INF32),
                    rng.integers(0, 8 * delta, size=s)).astype(np.int32)
    explored = np.where(rng.random(s) < 0.5, int(INF32),
                        dist + rng.integers(0, 2, size=s)).astype(np.int32)
    nbr = rng.integers(0, s, size=(s + 1, d_w)).astype(np.int32)
    w = rng.integers(0, 20, size=(s + 1, d_w)).astype(np.int32)
    nbr[s] = s
    w[s] = int(INF32)
    bucket_i = int(rng.integers(0, 4))
    pop = int(((dist < int(INF32)) & (dist // delta == bucket_i)
               & (dist < explored)).sum())
    for cap in sorted({1, max(1, pop), pop + 3}):
        got = frontier_relax(dist, explored, bucket_i, nbr, w, delta=delta,
                             cap=cap, base=base, backend="pallas",
                             interpret=True)
        want = frontier_relax_ref(dist, explored, bucket_i, nbr, w,
                                  delta=delta, cap=cap, base=base)
        tag = (seed, cap, pop)
        for name, a, b in zip(("fidx", "rows_n", "rows_w"), got, want):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b), err_msg=f"{tag}: {name}")
        assert int(got[3]) == int(want[3]) == pop, tag      # count
        assert bool(got[4]) == bool(want[4]) == (pop > 0), tag
        assert int(got[5]) == int(want[5]), tag             # next bucket


def test_kernel_zero_width_block_routes_to_oracle():
    """D == 0 ELL blocks (a side of the light/heavy split with no
    edges) have no TPU layout — the wrapper must dispatch the oracle
    and still report the scan outputs."""
    dist = np.asarray([0, 5, int(INF32)], np.int32)
    explored = np.full(3, int(INF32), np.int32)
    nbr = np.zeros((4, 0), np.int32)
    w = np.zeros((4, 0), np.int32)
    fidx, rows_n, rows_w, count, any_, nxt = frontier_relax(
        dist, explored, 0, nbr, w, delta=10, cap=2, backend="pallas",
        interpret=True)
    assert rows_n.shape == (2, 0) and rows_w.shape == (2, 0)
    assert int(count) == 2 and bool(any_)
    np.testing.assert_array_equal(np.asarray(fidx), [0, 1])


# ---------------------------------------------------------------------------
# engine: fused vs edge
# ---------------------------------------------------------------------------

def _star(k: int) -> COOGraph:
    """Source 0 fanning out to 1..k with weight 2: under Δ=2 the source
    settles alone in bucket 0 and the k leaves form bucket 1 by
    themselves — that bucket's frontier *and* settled set are exactly
    the k leaves, so a scratch of cap == k fills every slot in both the
    kernel compaction and the heavy-pass compaction without spilling."""
    src = np.zeros(k, np.int32)
    dst = np.arange(1, k + 1, dtype=np.int32)
    w = np.full(k, 2, np.int32)
    return COOGraph(src=src, dst=dst, w=w, n_nodes=k + 1)


@pytest.mark.parametrize("kernel_kw", [dict(interpret=True),
                                       dict(kernel_twin=True)],
                         ids=["kernel-interpret", "ref-twin"])
def test_fused_bitwise_equals_edge(kernel_kw):
    """Both fused execution paths (interpret-mode kernel; CPU oracle
    twin) reproduce ``edge`` bitwise — dist, packed pred words and the
    outer/inner counters (the atomic-iteration loop runs the same
    bucket schedule, DESIGN.md §12)."""
    g = watts_strogatz(300, 6, 0.05, seed=1)
    with enable_x64():
        base = _solve(g, 0, "edge", pred_mode="packed")
        res = _solve(g, 0, "fused", pred_mode="packed", **kernel_kw)
    _assert_bitwise(res, base, kernel_kw)
    assert not bool(res.overflow)


def test_exact_fill_frontier_no_overflow():
    """Regression: a frontier that exactly fills the compaction scratch
    (population == cap) is complete — right answer, no overflow flag.
    ``count`` must compare with ``>``, not ``>=``."""
    k = 13
    g = _star(k)
    base = _solve(g, 0, "edge", delta=2, pred_mode="argmin")
    for kw in (dict(interpret=True), dict(kernel_twin=True)):
        res = _solve(g, 0, "fused", delta=2, frontier_cap=k,
                     pred_mode="argmin", **kw)
        _assert_bitwise(res, base, kw)
        assert not bool(res.overflow), kw


def test_overflow_flag_one_past_fill():
    """One frontier member past the scratch (population == cap + 1)
    must raise the overflow flag — the truncated wave still drains the
    bucket, but the engine must report the capacity breach so the
    façade can demote."""
    k = 13
    g = _star(k)
    res = _solve(g, 0, "fused", delta=2, frontier_cap=k - 1,
                 pred_mode="argmin", interpret=True)
    assert bool(res.overflow)


def test_all_heavy_graph_zero_width_light_block():
    """Every weight > Δ: the light ELL block is zero-width and every
    relaxation happens in heavy passes — the fused driver loop must
    still settle buckets bitwise like ``edge``."""
    g = watts_strogatz(120, 4, 0.1, seed=5)
    g = COOGraph(src=g.src, dst=g.dst,
                 w=np.asarray(g.w, np.int32) + 50, n_nodes=g.n_nodes)
    base = _solve(g, 0, "edge", delta=3, pred_mode="argmin")
    res = _solve(g, 0, "fused", delta=3, pred_mode="argmin", interpret=True)
    _assert_bitwise(res, base, "all-heavy")


# ---------------------------------------------------------------------------
# façade: overflow demotes, never a wrong answer
# ---------------------------------------------------------------------------

def test_scratch_overflow_demotes_through_fallback():
    """A fused plan whose scratch cap is far too small trips overflow;
    with ``fallback=True`` the façade re-answers on the full-width twin
    and demotes permanently — the caller never sees a wrong answer."""
    g = watts_strogatz(300, 6, 0.05, seed=0)
    dref, _ = dijkstra(g, 0)
    cfg = DeltaConfig(delta=100, strategy="fused", frontier_cap=3,
                      interpret=True)
    plan = Engine(g, cfg).plan(fallback=True)
    res = plan.solve(SingleSource(0))
    assert res.telemetry.fallback
    np.testing.assert_array_equal(np.asarray(res.dist, np.int64), dref)
    assert plan.explain()["fallback_taken"]
    # parity default: the raw capped run only reports the flag
    raw = Engine(g, cfg).plan().solve(SingleSource(0))
    assert bool(np.asarray(raw.telemetry.overflow))
    assert not raw.telemetry.fallback


# ---------------------------------------------------------------------------
# warm repair: fused twin keeps the cold identity
# ---------------------------------------------------------------------------

def test_fused_warm_resolve_bitwise_cold_identity():
    """Weight updates on a fused plan resolve warm through a capped
    *fused* repair twin — and stay bitwise identical to a cold solve on
    the updated graph (DESIGN.md §11 lemma, fused instantiation)."""
    g = watts_strogatz(200, 6, 0.05, seed=7)
    cfg = DeltaConfig(delta=10, strategy="fused", interpret=True)
    plan = Engine(g, cfg).plan()
    plan.solve(SingleSource(0))
    edge_ids = np.asarray([3, 41, 97], np.int64)
    new_w = np.asarray(g.w)[edge_ids] + 7
    warm = plan.update(edge_ids, new_w).resolve(warm=True)
    g2 = COOGraph(src=g.src, dst=g.dst,
                  w=np.asarray(g.w).copy(), n_nodes=g.n_nodes)
    w2 = np.asarray(g2.w)
    w2[edge_ids] = new_w
    cold = Engine(
        COOGraph(src=g2.src, dst=g2.dst, w=w2.astype(np.int32),
                 n_nodes=g2.n_nodes), cfg).plan().solve(SingleSource(0))
    np.testing.assert_array_equal(np.asarray(warm.dist),
                                  np.asarray(cold.dist))


# ---------------------------------------------------------------------------
# mesh acceptance: sharded_fused @ 8 == fused @ 1
# ---------------------------------------------------------------------------

_ACCEPTANCE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    from repro.compat import enable_x64
    from repro.core import DeltaConfig, DeltaSteppingSolver
    from repro.graphs import rmat, watts_strogatz

    families = {
        "smallworld": (watts_strogatz(300, 6, 0.05, seed=0), 0, 10),
        "rmat": (rmat(256, 2500, seed=2), 0, 10),
    }
    with enable_x64():
        for name, (g, src, delta) in families.items():
            base = DeltaSteppingSolver(
                g, DeltaConfig(delta=delta, strategy="fused",
                               pred_mode="packed", interpret=True)
            ).solve(src)
            for kw in (dict(interpret=True), dict(kernel_twin=True)):
                cfg = DeltaConfig(delta=delta, strategy="sharded_fused",
                                  pred_mode="packed", n_shards=8, **kw)
                r = DeltaSteppingSolver(g, cfg).solve(src)
                for field in ("dist", "pred"):
                    a = np.asarray(getattr(r, field))
                    b = np.asarray(getattr(base, field))
                    assert np.array_equal(a, b), (name, kw, field)
                assert int(r.outer_iters) == int(base.outer_iters)
                assert int(r.inner_iters) == int(base.inner_iters)
    print("FUSED-ACCEPT-OK")
""")


def test_sharded_fused_acceptance_8_device_mesh_subprocess():
    """ISSUE 6 acceptance: ``sharded_fused`` at shards=8 is bitwise
    identical (packed dist+pred words, both counters) to ``fused`` at
    shards=1 on the paper graph families, for both the interpret-mode
    kernel and the oracle-twin execution paths."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", _ACCEPTANCE], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "FUSED-ACCEPT-OK" in out.stdout, out.stdout + out.stderr
