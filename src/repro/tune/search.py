"""Empirical (Δ, backend, frontier-packing) tuner (DESIGN.md §7).

The search space is the engine's perf-hillclimb axes: a geometric Δ grid
centred on the heuristic estimate (``estimator.estimate_delta``), the
relaxation backend (``edge`` / ``ell`` / optionally ``pallas``), and the
frontier packing — the compaction capacity of the ELL-family backends
(full |V| vs a fraction; candidates whose measured solve trips the
``overflow`` flag are rejected, never returned).

Pruning is successive halving: every surviving candidate is measured
with a short budget (one timed solve after a compile warm-up), the
slower half is dropped, the budget doubles, repeat until one remains.
Total measured work is ~2× the cost of timing every candidate once,
instead of the full-sweep cost of the paper's by-hand Fig. 1 protocol.

The result is a ``TuningRecord`` — a plain serializable fact about one
graph fingerprint — which ``cache.TuningCache`` persists and
``resolve_config`` turns back into a concrete ``DeltaConfig``.
Correctness does not depend on the tuner: every candidate config is
exact (tested bitwise-equal to the hand-picked engine), so tuning only
ever moves time, not answers.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Optional, Sequence, Tuple

import jax
import numpy as np

from repro.core.delta_stepping import DeltaConfig, DeltaSteppingSolver
from repro.graphs.structures import COOGraph
from repro.tune.estimator import (
    GraphStats,
    estimate_delta,
    fingerprint,
    graph_stats,
)

# Δ grid: estimate × these factors (deduplicated, floored at 1).
_DELTA_FACTORS = (0.25, 0.5, 1.0, 2.0, 4.0)

# Frontier packing: compaction capacity as a fraction of |V| (ELL-family
# backends only; 1.0 is the always-safe full-width buffer).
_CAP_FRACTIONS = (1.0, 0.25)

_MIN_CAP = 32


@dataclasses.dataclass(frozen=True)
class TuningRecord:
    """One tuned operating point for one graph fingerprint.

    ``source`` records provenance: ``'heuristic'`` (estimator only, zero
    measurement), ``'measured'`` (successive-halving search), or
    ``'cache'`` (loaded from a ``TuningCache``). ``trials`` keeps the
    latest (best-sampled) measurement of every viable candidate, sorted
    fastest-first — the evidence trail the cache file exposes for
    why-did-this-win inspection.
    """

    fingerprint: str
    delta: int
    strategy: str
    frontier_cap: Optional[int]
    source: str
    us_per_solve: Optional[float] = None
    trials: Tuple[Tuple[int, str, int, str, float], ...] = ()
    n_shards: Optional[int] = None
    # query-time axis (repro.landmarks): the measured point-to-point
    # algorithm choice of tune_p2p; None = never measured (early_exit)
    p2p_mode: Optional[str] = None
    # the algorithm axis (DESIGN.md §15): which frontier policy won.
    # Records predating the axis deserialize as 'delta' (the only
    # policy they could have measured).
    policy: str = "delta"

    def to_config(self, base: Optional[DeltaConfig] = None) -> DeltaConfig:
        """Concrete engine config: tuned (Δ, strategy, cap, mesh shape,
        policy, p2p mode) over the caller's base for everything else
        (pred_mode, ...)."""
        base = base if base is not None else DeltaConfig()
        return dataclasses.replace(
            base,
            delta=self.delta,
            strategy=self.strategy,
            frontier_cap=self.frontier_cap,
            n_shards=self.n_shards if self.n_shards is not None else base.n_shards,
            p2p_mode=self.p2p_mode if self.p2p_mode is not None else base.p2p_mode,
            policy=self.policy,
        )

    def to_json(self) -> dict:
        return {
            "fingerprint": self.fingerprint,
            "delta": self.delta,
            "strategy": self.strategy,
            "frontier_cap": self.frontier_cap,
            "source": self.source,
            "us_per_solve": self.us_per_solve,
            "trials": [list(t) for t in self.trials],
            "n_shards": self.n_shards,
            "p2p_mode": self.p2p_mode,
            "policy": self.policy,
        }

    @classmethod
    def from_json(cls, d: dict) -> "TuningRecord":
        def trial(row):
            # pre-policy records carried (Δ, strategy, cap, µs) rows
            if len(row) == 4:
                a, b, c, t = row
                p = "delta"
            else:
                a, b, c, p, t = row
            return (int(a), str(b), int(c), str(p), float(t))

        return cls(
            fingerprint=d["fingerprint"],
            delta=int(d["delta"]),
            strategy=d["strategy"],
            frontier_cap=(
                None if d.get("frontier_cap") is None else int(d["frontier_cap"])
            ),
            source=d.get("source", "cache"),
            us_per_solve=d.get("us_per_solve"),
            trials=tuple(trial(row) for row in d.get("trials", [])),
            n_shards=(None if d.get("n_shards") is None else int(d["n_shards"])),
            p2p_mode=d.get("p2p_mode"),
            policy=str(d.get("policy", "delta")),
        )


def heuristic_record(
    graph: COOGraph,
    base: Optional[DeltaConfig] = None,
    stats: Optional[GraphStats] = None,
) -> TuningRecord:
    """Zero-measurement record: estimator Δ, base strategy/packing.
    ``fingerprint`` is empty when the stats skipped the hop-radius probe
    (pure-heuristic path: nothing to key a cache with)."""
    base = base if base is not None else DeltaConfig()
    stats = stats if stats is not None else graph_stats(graph)
    return TuningRecord(
        fingerprint=fingerprint(stats) if stats.ecc0 >= 0 else "",
        delta=estimate_delta(stats),
        strategy=base.strategy,
        frontier_cap=base.frontier_cap,
        source="heuristic",
        policy=base.policy,
    )


def default_strategies() -> Tuple[str, ...]:
    """The tuner's default strategy axis: the single-device backends
    always, plus the mesh-sharded backends whenever the process actually
    has a mesh to shard over (>1 local device) — the mesh shape itself
    is pinned by the fingerprint's ``dev=`` term (DESIGN.md §9).

    The fused kernel strategies join only on a TPU, the one place their
    Pallas kernel compiles: off TPU they would measure the interpreter
    or the jnp twin, which says nothing about the kernel, so they are
    left out here rather than substituted (core.backends
    ``kernel_path``)."""
    import jax

    single = ("edge", "ell")
    sharded = ("sharded_edge", "sharded_ell")
    if jax.default_backend() == "tpu":
        single += ("fused",)
        sharded += ("sharded_fused",)
    return single + sharded if jax.device_count() > 1 else single


def default_policies() -> Tuple[str, ...]:
    """The tuner's algorithm axis (DESIGN.md §15): every frontier policy
    competes by default — each one is exact, so the axis only ever moves
    time."""
    return ("delta", "rho", "radius")


def candidate_configs(
    stats: GraphStats,
    strategies: Optional[Sequence[str]] = None,
    deltas: Optional[Sequence[int]] = None,
    cap_fractions: Sequence[float] = _CAP_FRACTIONS,
    policies: Optional[Sequence[str]] = None,
) -> list:
    """The (Δ, strategy, frontier_cap, policy) grid the tuner searches.
    Edge strategy ignores packing (no compaction), so it contributes one
    candidate per Δ; ELL-family strategies get one per cap fraction.
    The sharded strategies contribute one candidate per Δ at full mesh
    width (``sharded_ell``'s per-shard buffer is already |V|/P wide —
    fractional caps would mostly re-measure overflow rejections).

    The non-delta policies do not bucket, so Δ only moves their
    light/heavy edge-phase split — they enter at the single central Δ of
    the grid rather than multiplying the whole Δ axis."""
    if strategies is None:
        strategies = default_strategies()
    if deltas is None:
        est = estimate_delta(stats)
        deltas = sorted({max(1, int(round(est * f))) for f in _DELTA_FACTORS})
    if policies is None:
        policies = default_policies()
    n = stats.n_nodes
    out = []
    for policy in policies:
        pol_deltas = (
            deltas if policy == "delta"
            else [sorted(deltas)[len(deltas) // 2]]
        )
        for delta in pol_deltas:
            for strat in strategies:
                if strat in ("ell", "pallas", "fused"):
                    for frac in cap_fractions:
                        cap = (None if frac >= 1.0
                               else max(_MIN_CAP, int(n * frac)))
                        out.append((delta, strat, cap, policy))
                else:
                    out.append((delta, strat, None, policy))
    return out


def _solver_no_warn(graph, cfg, free_mask=None):
    """Internal solver construction: the tuner measures through the
    legacy solver shape on purpose (one warmed object per candidate),
    which must not surface the shim's DeprecationWarning to callers of
    the supported tuning entry points."""
    import warnings

    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return DeltaSteppingSolver(graph, cfg, free_mask=free_mask)


def _candidate_solver(graph, cfg, sources, free_mask=None):
    """Build + warm up + validate one candidate's solver; ``None`` when
    the config overflows its frontier cap for *any* probe source — an
    overflowed run is a wrong-answer run and its time must never
    compete. Build and compile errors propagate: a candidate the
    compiler refuses is a fault to see, not a slow candidate."""
    solver = _solver_no_warn(graph, cfg, free_mask=free_mask)
    for s in sources:  # warm up / compile + validate every source
        if bool(solver.solve(int(s)).overflow):
            return None
    return solver


def _time_solver(solver, sources, reps: int) -> float:
    """Median seconds per solve on an already-warm solver."""
    times = []
    for _ in range(reps):
        for s in sources:
            t0 = time.perf_counter()
            jax.block_until_ready(solver.solve(int(s)).dist)
            times.append(time.perf_counter() - t0)
    return float(np.median(times))


def build_safe_solver(
    graph: COOGraph,
    cfg: DeltaConfig,
    *,
    sources: Sequence[int] = (0,),
    free_mask=None,
):
    """Build a solver whose ``frontier_cap`` is validated against the
    caller's actual sources, not just the tuner's probe sources — the
    one shared enforcement point of the 'tuning may move time, never
    answers' invariant (a cached record can come from a same-fingerprint
    graph, and a capped winner was only ever validated on the sources it
    was measured with). Returns ``(config, solver)``; on overflow the
    cap is dropped and the solver rebuilt full-width. Consumers with a
    *dynamic* source stream (serve.SSSPServer) instead re-check the
    overflow flag per batch at serve time."""

    def build(c):
        return _solver_no_warn(
            graph,
            c,
            free_mask=free_mask if c.strategy == "pallas" else None,
        )

    solver = build(cfg)
    if cfg.frontier_cap is not None:
        srcs = [int(s) for s in sources]
        if len(srcs) > 1:
            over = solver.solve_many(np.asarray(srcs, np.int32)).overflow
            tripped = bool(np.any(np.asarray(over)))
        else:
            tripped = bool(solver.solve(srcs[0]).overflow)
        if tripped:
            cfg = dataclasses.replace(cfg, frontier_cap=None)
            solver = build(cfg)
    return cfg, solver


def tune(
    graph: COOGraph,
    base: Optional[DeltaConfig] = None,
    *,
    sources: Sequence[int] = (0,),
    strategies: Optional[Sequence[str]] = None,
    deltas: Optional[Sequence[int]] = None,
    cap_fractions: Sequence[float] = _CAP_FRACTIONS,
    policies: Optional[Sequence[str]] = None,
    cache=None,
    free_mask=None,
    measure_fn=None,
) -> TuningRecord:
    """Measured search with successive-halving pruning.

    ``base`` supplies the non-searched config fields (pred_mode is
    forced to ``'none'`` during measurement — predecessor recovery is
    off the timed path — and restored by ``TuningRecord.to_config``).
    ``strategies=None`` searches ``default_strategies()``: the mesh-
    sharded backends join the space whenever >1 device is present.
    ``policies=None`` searches ``default_policies()`` — the algorithm
    axis rides the same halving loop as (Δ, strategy, cap).
    ``cache`` (a ``TuningCache``-shaped object) is consulted before the
    search and updated — and saved — after it. ``measure_fn`` overrides
    the timing primitive (tests inject deterministic costs); its
    signature is ``(delta, strategy, cap, policy, reps) -> seconds``.
    """
    base = base if base is not None else DeltaConfig()
    stats = graph_stats(graph)
    fp = fingerprint(stats)
    if cache is not None:
        hit = cache.get(fp)
        if hit is not None:
            return dataclasses.replace(hit, source="cache")

    bench_cfg = dataclasses.replace(base, pred_mode="none")
    if measure_fn is None:
        # one solver (and one compile + overflow validation) per
        # candidate for the whole search: later halving rounds re-time
        # the warm solver instead of re-paying the build
        solvers = {}

        def measure_fn(delta, strat, cap, policy, reps):
            key = (delta, strat, cap, policy)
            if key not in solvers:
                cfg = dataclasses.replace(
                    bench_cfg, delta=delta, strategy=strat,
                    frontier_cap=cap, policy=policy,
                )
                solvers[key] = _candidate_solver(
                    graph, cfg, sources, free_mask=free_mask
                )
            if solvers[key] is None:  # overflowed: never competes
                return float("inf")
            return _time_solver(solvers[key], sources, reps)

    survivors = candidate_configs(
        stats, strategies=strategies, deltas=deltas,
        cap_fractions=cap_fractions, policies=policies,
    )
    reps = 1
    evidence = {}  # candidate -> its latest (best-sampled) measurement
    timed = []
    while True:
        timed = [(measure_fn(d, s, c, p, reps), (d, s, c, p))
                 for d, s, c, p in survivors]
        timed.sort(key=lambda x: x[0])
        timed = [t for t in timed if np.isfinite(t[0])]
        evidence.update({cand: t for t, cand in timed})
        if not timed:
            # every candidate overflowed: fall back to heuristic
            return heuristic_record(graph, base, stats)
        if len(timed) == 1:
            break
        survivors = [cand for _, cand in timed[: (len(timed) + 1) // 2]]
        if len(survivors) == 1:
            # one final, better-sampled measurement of the winner
            reps *= 2
            d, s, c, p = survivors[0]
            timed = [(measure_fn(d, s, c, p, reps), (d, s, c, p))]
            evidence[(d, s, c, p)] = timed[0][0]
            break
        reps *= 2

    best_t, (delta, strat, cap, policy) = timed[0]
    if strat.startswith("sharded"):
        # pin the mesh width the winner was actually measured on
        from repro.core.backends import resolve_n_shards

        shards = resolve_n_shards(base.n_shards)
    else:
        shards = None
    record = TuningRecord(
        fingerprint=fp,
        delta=delta,
        strategy=strat,
        frontier_cap=cap,
        source="measured",
        us_per_solve=round(best_t * 1e6, 1),
        trials=tuple(
            (d, s, -1 if c is None else c, p, round(t * 1e6, 1))
            for (d, s, c, p), t in sorted(evidence.items(), key=lambda kv: kv[1])
        ),
        n_shards=shards,
        policy=policy,
    )
    if cache is not None:
        cache.put(record)
        cache.save()
    return record


def resolve_record(
    graph: COOGraph,
    base: Optional[DeltaConfig] = None,
    *,
    free_mask=None,
    cache_path: Optional[str] = None,
    measure: bool = False,
    sources: Optional[Sequence[int]] = (0,),
) -> Tuple[DeltaConfig, TuningRecord]:
    """The ``config="auto"`` resolution path: cache hit → tuned config;
    otherwise the zero-measurement estimator (or, with ``measure=True``,
    the successive-halving search, persisted when a cache path is
    given). Returns ``(config, record)`` — the concrete operating point
    plus the ``TuningRecord`` it came from, so the caller (a
    ``repro.api.Plan``) can attach the tuning evidence to the unit that
    serves with it.

    A tuning-chosen ``frontier_cap`` never reaches the engine
    unvalidated (cache records can come from a same-fingerprint graph
    the cap was never checked on): with ``sources`` given, the cap is
    re-validated against exactly those sources (one warm solve on the
    shared ``build_safe_solver`` path) and dropped on overflow; with
    ``sources=None`` — a caller that cannot know its future sources,
    like the core ``config="auto"`` path — the cap is dropped outright.
    Tuning may move time, never answers."""
    base = base if base is not None else DeltaConfig()
    if cache_path is not None or measure:
        from repro.tune.cache import TuningCache

        cache = TuningCache(cache_path)
        probe = sources if sources is not None else (0,)
        if measure:
            rec = tune(
                graph, base, sources=probe, cache=cache, free_mask=free_mask
            )
        else:
            stats = graph_stats(graph)
            rec = cache.get(fingerprint(stats))
            if rec is None:
                rec = heuristic_record(graph, base, stats)
        cfg = rec.to_config(base)
        # only a record fresh from THIS call's measured search was
        # already validated against these probe sources (tune() re-marks
        # its own cache hits source="cache"; a measure=False cache.get
        # returns the stored record with its original source, which
        # proves nothing about this graph). sources=None callers can
        # never trust a cap: the probe covered (0,) only.
        tuned_cap = (
            cfg.frontier_cap is not None and cfg.frontier_cap != base.frontier_cap
        )
        validated = measure and rec.source == "measured" and sources is not None
        if tuned_cap and not validated:
            if sources is None:
                cfg = dataclasses.replace(cfg, frontier_cap=None)
            else:
                cfg, _ = build_safe_solver(
                    graph, cfg, sources=sources, free_mask=free_mask
                )
        return cfg, rec
    # pure-heuristic path: degrees and weights are enough — skip the
    # O(diameter·|E|) hop-radius probe (no cache key to build)
    stats = graph_stats(graph, probe_ecc=False)
    rec = heuristic_record(graph, base, stats)
    return rec.to_config(base), rec


def resolve_config(
    graph: COOGraph,
    base: Optional[DeltaConfig] = None,
    *,
    free_mask=None,
    cache_path: Optional[str] = None,
    measure: bool = False,
    sources: Optional[Sequence[int]] = (0,),
) -> DeltaConfig:
    """Config-only wrapper of :func:`resolve_record` (the original
    ``config="auto"`` entry point; kept for callers that do not track
    tuning evidence)."""
    cfg, _ = resolve_record(
        graph,
        base,
        free_mask=free_mask,
        cache_path=cache_path,
        measure=measure,
        sources=sources,
    )
    return cfg


def tune_p2p(
    graph: COOGraph,
    record: Optional[TuningRecord] = None,
    *,
    pairs: Optional[Sequence[Tuple[int, int]]] = None,
    modes: Optional[Sequence[str]] = None,
    reps: int = 3,
    landmarks: Optional[dict] = None,
    free_mask=None,
    cache=None,
    measure_fn=None,
) -> TuningRecord:
    """Measured query-time search over the point-to-point algorithm
    (``DeltaConfig.p2p_mode``, DESIGN.md §14): times each candidate mode
    on representative ``pairs`` through a real Plan and records the
    winner on the tuning record, so cached workloads pick their p2p
    algorithm the same way they pick (Δ, strategy, cap).

    ``record`` is the operating point to extend (default: the
    zero-measurement heuristic record); ``modes=None`` searches
    ``early_exit`` plus — on canonical (w >= 1) graphs — the three
    landmark modes; ``landmarks`` are ``Plan.prepare_landmarks`` kwargs
    (table precompute runs once, before the clock starts — ALT query
    time is what is being measured, exactly the preprocessing/query
    split of the goal-directed literature). ``cache`` (a ``TuningCache``
    or path) persists the extended record under the same fingerprint.
    ``measure_fn(mode) -> seconds`` overrides the timing primitive
    (tests inject deterministic costs). Every mode returns bitwise-equal
    distances, so — like the rest of the tuner — this only ever moves
    time, not answers.
    """
    from repro.api import Engine, PointToPoint  # lazy: api builds on tune
    from repro.core.backends import graph_is_canonical

    if record is None:
        record = heuristic_record(graph)
    base = record.to_config(DeltaConfig(pred_mode="none"))
    if modes is None:
        modes = ("early_exit",)
        if graph_is_canonical(graph):
            from repro.landmarks import LANDMARK_MODES

            modes = modes + LANDMARK_MODES
    n = graph.n_nodes
    if pairs is None:
        rng = np.random.default_rng(0)
        pairs = tuple(
            (int(rng.integers(n)), int(rng.integers(n))) for _ in range(4)
        )
    plan = Engine(graph, base, free_mask=free_mask).plan()
    if landmarks:
        plan.prepare_landmarks(**landmarks)
    best_mode, best_t = None, None
    for mode in modes:
        if measure_fn is not None:
            elapsed = float(measure_fn(mode))
        else:

            def run(m=mode):
                for s, t in pairs:
                    plan.solve(PointToPoint(s, t, mode=m))

            run()  # compile warm-up (and lazy table build) off the clock
            elapsed = None
            for _ in range(max(1, reps)):
                t0 = time.perf_counter()
                run()
                dt = time.perf_counter() - t0
                elapsed = dt if elapsed is None else min(elapsed, dt)
        if best_t is None or elapsed < best_t:
            best_mode, best_t = mode, elapsed
    record = dataclasses.replace(record, p2p_mode=best_mode)
    if cache is not None:
        if isinstance(cache, str):
            from repro.tune.cache import TuningCache

            cache = TuningCache(cache)
        cache.put(record)
        cache.save()
    return record
