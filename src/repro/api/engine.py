"""The Query/Plan façade — the one public entry point of the engine.

``Engine(graph).plan()`` resolves tuning / strategy / caps exactly once
and returns a ``Plan`` holding the pre-lowered jitted drivers (the
module-level jitted programs of ``core.delta_stepping``, so plans over
same-shaped graphs share compile cache entries exactly like the
deprecated ``DeltaSteppingSolver`` did); ``plan.solve(query)``
dispatches on the small query algebra of ``queries.py``.

Resolution (DESIGN.md §7/§10) happens in one place, ``Engine.plan``,
steered by the one ``tuning=`` knob:

* a concrete ``DeltaConfig`` with ``tuning=None`` is used as-is;
* ``tuning="auto"`` / ``"measure"`` / a cache path / a ``Tuning(...)``
  — with any concrete config acting as the tuning *base* — goes through
  ``tune.resolve_record``, whose cap validation runs on the one shared
  ``build_safe_solver`` path: a tuning-chosen ``frontier_cap`` is
  re-validated against ``plan(sources=...)`` (and dropped on overflow)
  or dropped outright when the plan cannot know its future sources.
  The winning ``TuningRecord`` attaches to the plan (``plan.record``) —
  a Plan is the unit tuning evidence hangs off. The pre-redesign
  spellings (``config="auto"``, ``tune=``, ``tune_cache=``) are
  deprecated shims onto exactly these semantics.

Overflow handling has one fallback point, ``Plan.solve``: with
``fallback=True`` (the serving configuration) a query whose solve trips
the compacted-frontier ``overflow`` flag is re-answered by a full-width
twin plan and the plan demotes to it permanently — capped solves may
move time, never answers. With ``fallback=False`` (the parity default)
the flag is reported in the result telemetry and the caller decides,
exactly like the pre-façade solver.

Dynamic graphs (repro.dynamic, DESIGN.md §11): a plan is also the unit
of *residency*. Solving ``SingleSource`` keeps the converged answer and
a weight snapshot on the plan; ``plan.update(edge_ids, new_weights)``
swaps edge costs (topology fixed), and ``plan.resolve(warm=True)``
re-solves the resident problem by warm-start repair — bitwise identical
to a cold solve of the updated graph, at the cost of the repair cone
instead of the whole instance. ``plan.solve(UpdateBatch(...))`` is the
query-algebra packaging of the same pair.
"""

from __future__ import annotations

import dataclasses
import warnings
from functools import partial
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.api.paths import extract_path, stitch_bidirectional_path
from repro.api.queries import (
    BoundedRadius,
    BoundedRadiusResult,
    ManyToMany,
    ManyToManyResult,
    MultiSource,
    MultiSourceResult,
    PointToPoint,
    PointToPointResult,
    Query,
    Result,
    SingleSource,
    SingleSourceResult,
    Telemetry,
    UpdateBatch,
)
from repro.core.backends import dist_of, make_backend
from repro.core.delta_stepping import (
    DeltaConfig,
    _finish_pred,
    _finish_pred_many,
    _require_x64,
    _run_many_seq,
    _run_many_vmapped,
    _run_one,
    _run_one_bounded,
    _run_one_p2p,
    _run_one_warm,
    _run_policy_bounded,
    _run_policy_many_seq,
    _run_policy_many_vmapped,
    _run_policy_one,
    _run_policy_p2p,
    _run_policy_warm,
    pred_argmin,
)
from repro.core.policies import RadiiStore, make_policy
from repro.dynamic import Resident, apply_weight_update, plan_repair
from repro.graphs.structures import COOGraph, INF32


class UpdateRefused(ValueError):
    """Structured refusal of a dynamic update the plan cannot apply.

    ``reason`` is a stable machine-readable tag (currently
    ``"grid_costs"``: grid-stencil plans take their costs from
    ``DeltaConfig.grid_costs``, not the COO weight array). The serving
    tier keys on it to shed the offending request per-ticket instead of
    treating the refusal as a batch-loop failure; direct callers still
    get an ordinary ``ValueError`` (this is a subclass).

    >>> try:
    ...     raise UpdateRefused("no", reason="grid_costs")
    ... except ValueError as e:
    ...     e.reason
    'grid_costs'
    """

    def __init__(self, message: str, *, reason: str):
        super().__init__(message)
        self.reason = reason


class LandmarkRefused(UpdateRefused):
    """Refusal of a weight batch that would invalidate the plan's
    landmark tables under the ``on_update="refuse"`` policy
    (``Plan.prepare_landmarks``): some new weight drops below its
    table-build value, so the precomputed ALT potentials would stop
    being admissible. Raised BEFORE any weight is applied — the plan
    (and its tables) are untouched, and the serving tier sheds the
    ticket on the standard ``UpdateRefused`` path.

    >>> try:
    ...     raise LandmarkRefused("no", reason="landmarks_stale")
    ... except UpdateRefused as e:
    ...     e.reason
    'landmarks_stale'
    """


@dataclasses.dataclass(frozen=True)
class Tuning:
    """The one tuning knob of ``Engine``: how the operating point is
    resolved. ``measure=True`` runs the successive-halving measured
    search (DESIGN.md §7); ``cache`` names the persistent fingerprint-
    keyed record store consulted first (and, for measured searches,
    written back). ``Tuning()`` — no measurement, no cache — is the
    zero-measurement estimator, spelled ``tuning="auto"`` for short;
    ``tuning="measure"`` is ``Tuning(measure=True)``; any other string
    is taken as a cache path.

    >>> Tuning(measure=True, cache="tuning.json").measure
    True
    """

    measure: bool = False
    cache: Optional[str] = None


def _normalize_tuning(tuning) -> Optional[Tuning]:
    if tuning is None or isinstance(tuning, Tuning):
        return tuning
    if isinstance(tuning, str):
        if tuning == "auto":
            return Tuning()
        if tuning == "measure":
            return Tuning(measure=True)
        return Tuning(cache=tuning)
    raise ValueError(
        "tuning must be None, 'auto', 'measure', a cache path or a "
        f"Tuning(...), got {tuning!r}"
    )


def _mark_fallback(res: Result) -> Result:
    tel = dataclasses.replace(res.telemetry, fallback=True)
    return dataclasses.replace(res, telemetry=tel)


def _check_vertex(name: str, v, n: int) -> int:
    """Host-side id validation: out-of-range ids would otherwise be
    silently dropped by the jitted scatter (an all-INF 'answer') or
    clamped by the gather (a wrong early exit)."""
    v = int(v)
    if not 0 <= v < n:
        raise ValueError(f"{name} {v} out of range for a {n}-vertex graph")
    return v


def _check_vertices(name: str, arr: np.ndarray, n: int) -> None:
    if arr.size and (int(arr.min()) < 0 or int(arr.max()) >= n):
        raise ValueError(f"{name} contain ids out of range, graph has {n}")


class Plan:
    """A compiled operating point for one graph: resolved config,
    relaxation backend, and partially-applied module-level jitted
    drivers for every query kind. Built by ``Engine.plan``; solvable
    immediately and repeatedly via ``solve(query)``, and updatable in
    place for dynamic edge costs via ``update`` / ``resolve``.

    >>> import jax.numpy as jnp
    >>> from repro.api import Engine, SingleSource
    >>> from repro.core import DeltaConfig
    >>> from repro.graphs.structures import COOGraph
    >>> g = COOGraph(jnp.array([0, 1], jnp.int32),
    ...              jnp.array([1, 2], jnp.int32),
    ...              jnp.array([2, 3], jnp.int32), 3)
    >>> plan = Engine(g, DeltaConfig(delta=4, pred_mode="argmin")).plan()
    >>> res = plan.solve(SingleSource(0))
    >>> [int(d) for d in res.dist]
    [0, 2, 5]
    >>> plan.update([0], [9]) is plan       # edge 0->1 now costs 9
    True
    >>> warm = plan.resolve(warm=True)      # repair, not a cold re-solve
    >>> [int(d) for d in warm.dist], bool(warm.telemetry.warm)
    ([0, 9, 12], True)
    """

    def __init__(
        self,
        graph: COOGraph,
        config: DeltaConfig,
        *,
        free_mask=None,
        record=None,
        fallback: bool = False,
        radii_store: Optional[str] = None,
    ):
        if config.pred_mode == "packed":
            _require_x64()
        if config.policy != "delta" and (
            free_mask is not None and config.strategy == "pallas"
        ):
            # the grid stencil recomputes bucket membership in-kernel
            # from tent // Δ — it has no frontier-mask input a policy
            # loop could drive
            raise ValueError(
                "the grid-stencil game-map path is delta-only; "
                f"policy={config.policy!r} needs a mask-driven backend"
            )
        self.graph = graph
        self.config = config
        self.record = record
        self.free_mask = free_mask
        self.backend = make_backend(graph, config, free_mask=free_mask)
        packed = config.pred_mode == "packed"
        self._packed = packed
        # frontier policy (DESIGN.md §15): 'delta' binds the classic
        # bucket-loop drivers (bit-for-bit the pre-policy plan); rho /
        # radius bind the generic policy loop over the same backend.
        # radius preprocessing persists beside the tuner cache when the
        # engine hands a store directory down.
        self._radii_store = radii_store
        self._policy = make_policy(
            graph, config,
            store=None if radii_store is None else RadiiStore(radii_store),
        )
        self._bind_drivers()
        # the one overflow-fallback point: only meaningful when a capped
        # compaction can actually overflow
        self._fallback = bool(fallback) and config.frontier_cap is not None
        self._demoted: Optional[Plan] = None
        # dynamic residency (repro.dynamic): the last SingleSource answer
        # plus the weight snapshot it was solved against
        self._resident: Optional[Resident] = None
        # warm-repair twin backend cache: (cap, graph version) -> backend
        self._graph_version = 0
        self._repair_twin = None
        self._repair_twin_key = None
        self._twin_width = None   # weight-independent ELL pad width
        self._twin_cap_floor = 64  # escalates on twin overflow (sticky)
        # landmark residency (repro.landmarks, DESIGN.md §14): built by
        # prepare_landmarks, or lazily with defaults on the first
        # landmark-mode PointToPoint
        self._landmarks = None

    def _bind_drivers(self) -> None:
        """Partially apply the module-level jitted drivers for the
        plan's policy. Every query kind dispatches through these five
        attributes, so the policy axis is invisible past this point."""
        n = self.graph.n_nodes
        packed = self._packed
        if self.config.policy == "delta":
            self._run1 = partial(_run_one, n=n, packed=packed)
            many = (_run_many_vmapped if self.backend.supports_vmap
                    else _run_many_seq)
            self._run_many = partial(many, n=n, packed=packed)
            self._run_p2p = partial(_run_one_p2p, n=n, packed=packed)
            self._run_bounded = partial(_run_one_bounded, n=n, packed=packed)
            self._run_warm = partial(_run_one_warm, n=n, packed=packed)
        else:
            pol = self._policy
            self._run1 = partial(_run_policy_one, policy=pol, n=n,
                                 packed=packed)
            many = (_run_policy_many_vmapped if self.backend.supports_vmap
                    else _run_policy_many_seq)
            self._run_many = partial(many, policy=pol, n=n, packed=packed)
            self._run_p2p = partial(_run_policy_p2p, policy=pol, n=n,
                                    packed=packed)
            self._run_bounded = partial(_run_policy_bounded, policy=pol,
                                        n=n, packed=packed)
            self._run_warm = partial(_run_policy_warm, policy=pol, n=n,
                                     packed=packed)

    # -- the one public operation -------------------------------------------

    def solve(self, query: Query) -> Result:
        """Answer one query. With fallback enabled, a solve that trips
        the compacted-frontier overflow flag is re-answered by the
        full-width twin plan (and the plan demotes to it permanently —
        a query mix that overflowed once would otherwise pay capped +
        uncapped solves on every call).

        ``UpdateBatch`` is the one query kind that mutates the plan: it
        routes through ``update`` + ``resolve`` and does not participate
        in overflow demotion (the warm contract itself refuses an
        overflowed resident state, and the overflow flag of the re-solve
        is reported in its telemetry).

        Under ``jax.profiler`` the call is a ``plan.solve`` host span
        (with the query kind), and a demotion a ``plan.demote`` span
        inside it: the twin's build and its first solve, which compiles
        the full-width program."""
        with TraceAnnotation("plan.solve", kind=type(query).__name__):
            return self._solve(query)

    def _solve(self, query: Query) -> Result:
        if isinstance(query, UpdateBatch):
            self.update(query.edge_ids, query.new_weights)
            return self.resolve(warm=query.warm)
        if self._demoted is not None:
            return _mark_fallback(self._demoted._dispatch(query))
        res = self._dispatch(query)
        if self._fallback and bool(np.any(np.asarray(res.telemetry.overflow))):
            with TraceAnnotation("plan.demote"):
                self._demoted = Plan(
                    self.graph,
                    dataclasses.replace(self.config, frontier_cap=None),
                    free_mask=self.free_mask,
                    record=self.record,
                    radii_store=self._radii_store,
                )
                # residency survives demotion: the resident answer was
                # solved on the same graph/pred_mode (only the cap
                # differs), so update/resolve keep working after an
                # overflow demotes
                self._demoted._resident = self._resident
                # landmark residency too: tables/spec only depend on the
                # graph, never on the frontier cap
                self._demoted._landmarks = self._landmarks
                res = _mark_fallback(self._demoted._dispatch(query))
        return res

    # -- dynamic updates (repro.dynamic, DESIGN.md §11) ----------------------

    def update(self, edge_ids, new_weights) -> "Plan":
        """Apply an edge-cost update batch to the plan in place: swap
        weights (topology fixed), rebuild the relaxation backend on the
        updated graph, and leave the resident answer untouched until the
        next ``resolve`` diffs against its snapshot — so several update
        batches between resolves compose naturally. Returns ``self``.

        The tuning record attached at plan time stays attached: the
        cache fingerprint keys on structural statistics and the weight
        *range*, so in-range cost churn reuses the record
        (tests/test_dynamic.py asserts this)."""
        if self.free_mask is not None and self.config.strategy == "pallas":
            raise UpdateRefused(
                "grid-stencil (game-map) plans take their costs from "
                "DeltaConfig.grid_costs, not the COO weight array — "
                "edge-weight updates do not apply to them",
                reason="grid_costs",
            )
        lm = self._landmarks
        if (lm is not None and lm.spec.on_update == "refuse"
                and lm.would_invalidate(edge_ids, new_weights)):
            # checked BEFORE applying: a refused batch leaves both the
            # weights and the landmark tables exactly as they were
            raise LandmarkRefused(
                "weight decrease below the landmark-table build values "
                "would invalidate the precomputed ALT potentials (plan "
                "prepared with on_update='refuse')",
                reason="landmarks_stale",
            )
        self.graph = apply_weight_update(self.graph, edge_ids, new_weights)
        self.backend = self._rebuild_backend()
        self._graph_version += 1
        if self.config.policy == "radius":
            # step radii derive from the weights: recompute (or re-fetch)
            # and rebind the drivers. The radius policy keeps r as a
            # pytree *leaf*, so the rebinding swaps arrays without
            # retracing the compiled loop.
            self._policy = make_policy(
                self.graph, self.config,
                store=(None if self._radii_store is None
                       else RadiiStore(self._radii_store)),
            )
            self._bind_drivers()
        if lm is not None:
            lm.note_update(self.graph)
        if self._demoted is not None:
            self._demoted.update(edge_ids, new_weights)
        return self

    def _rebuild_backend(self):
        """Backend over the updated weights. The ELL-family strategies
        pad their light/heavy blocks to the tightest per-block width by
        default — a width that *moves with edge costs* (the split is
        w <= Δ), which would retrace the jitted drivers on every update
        batch. Rebuilds pin the weight-independent full adjacency
        degree instead: the first update may recompile once (wider
        shapes than the plan-time build), every later one reuses it.
        The ``edge`` strategy's light/heavy halves have the same
        problem in their lengths; rebuilds pad both to capacity m (an
        updated plan sweeps m slots per phase, the unsplit cost).
        ``sharded_ell`` keeps the default partition build and may
        retrace when the split widths move."""
        if self.config.strategy == "edge":
            from repro.core.backends import EdgeBackend

            return EdgeBackend.build(
                self.graph, self.config, capacity=self.graph.n_edges
            )
        if self.config.strategy == "ell":
            from repro.core.backends import EllBackend

            return EllBackend.build(
                self.graph, self.config, max_deg=self._adjacency_width()
            )
        if self.config.strategy == "pallas" and self.free_mask is None:
            from repro.core.backends import PallasEllBackend

            return PallasEllBackend.build(
                self.graph, self.config, max_deg=self._adjacency_width()
            )
        if self.config.strategy == "fused":
            from repro.core.backends import FusedBackend

            return FusedBackend.build(
                self.graph, self.config, max_deg=self._adjacency_width()
            )
        return make_backend(self.graph, self.config, free_mask=self.free_mask)

    def _adjacency_width(self) -> int:
        """Max out-degree — the weight-independent ELL pad width
        (topology never changes, so compute once per plan)."""
        if self._twin_width is None:
            deg = np.bincount(
                np.asarray(self.graph.src), minlength=self.graph.n_nodes
            )
            self._twin_width = max(1, int(deg.max())) if deg.size else 1
        return self._twin_width

    def _warm_backend(self, repaired: int):
        """Backend for a warm repair solve. Repair frontiers are tiny
        (the whole point), so for the single-device strategies the sweep
        runs on a frontier-compacted ELL twin whose capacity is a small
        power-of-two head-room over the seed count — O(cap·deg) work per
        sweep instead of O(|E|). Safe because the overflow flag guards a
        full-width re-run in ``resolve``, and *exact* because every
        warm-eligible mode has a schedule-free fixed point (dist always;
        packed words on the canonical class — DESIGN.md §11), so the
        twin converges to bitwise the same answer as the plan's own
        backend. A ``fused`` plan gets a capped *fused* twin — staying
        on the fused driver loop keeps the warm solve on the exact code
        path the cold-identity lemma was checked against.
        Sharded/pallas plans keep their own backend."""
        if self.config.strategy not in ("edge", "ell", "fused"):
            return self.backend
        n = self.graph.n_nodes
        cap = self._twin_cap_floor
        while cap < repaired * 2:
            cap *= 2
        own_cap = self.config.frontier_cap or n
        if cap >= n or (
            self.config.strategy in ("ell", "fused") and cap >= own_cap
        ):
            return self.backend
        key = (cap, self._graph_version)
        if self._repair_twin_key != key:
            from repro.core.backends import EllBackend, FusedBackend

            twin_strategy = "fused" if self.config.strategy == "fused" else "ell"
            twin_cls = FusedBackend if twin_strategy == "fused" else EllBackend
            twin_cfg = dataclasses.replace(
                self.config, strategy=twin_strategy, frontier_cap=cap
            )
            # pinned pad width: cost churn must not move the twin's
            # compiled shapes (see _rebuild_backend)
            self._repair_twin = twin_cls.build(
                self.graph, twin_cfg, max_deg=self._adjacency_width()
            )
            self._repair_twin_key = key
        return self._repair_twin

    def resolve(self, warm: bool = True) -> SingleSourceResult:
        """Re-solve the plan's resident single-source problem against
        the current (updated) weights. ``warm=True`` repairs from the
        resident answer: changed edges seed a repair frontier (decreases
        enter their new bucket directly; increases reset and re-seed the
        predecessor-tree cone) and the generalized bucket loop re-settles
        only what the perturbation can reach — bitwise identical to the
        cold solve, per the repro.dynamic contract. Updates outside the
        warm contract (see ``dynamic.plan_repair``) re-solve cold;
        telemetry reports which path ran (``warm``/``repaired``/``cone``).
        """
        if self._demoted is not None:
            return _mark_fallback(self._demoted.resolve(warm=warm))
        r = self._resident
        if r is None:
            raise ValueError(
                "resolve() repairs the plan's resident state — solve a "
                "SingleSource query first to establish it"
            )
        src = jnp.asarray(r.source, jnp.int32)
        rep = reason = None
        if warm:
            rep, reason = plan_repair(
                self.graph, r, pred_mode=self.config.pred_mode
            )
        if rep is not None and rep.repaired == 0:
            # distance-neutral churn: distances stand as-is. argmin
            # preds are a function of (distances, *current* weights),
            # and a tie can move without any distance moving (a decrease
            # landing exactly on dist[v] creates a new smaller-id tight
            # parent) — recompute the tree against the updated graph;
            # packed ties are covered by the repair's word-order seeds,
            # and 'none' tracks no tree
            dist = jnp.asarray(r.dist, jnp.int32)
            if self.config.pred_mode == "argmin":
                g = self.graph
                pred = pred_argmin(
                    dist, g.src, g.dst, g.w, src, n=g.n_nodes
                )
            else:
                pred = jnp.asarray(r.pred, jnp.int32)
            self._remember(r.source, dist, pred, r.overflow)
            zero = jnp.zeros((), jnp.int32)
            return SingleSourceResult(
                dist,
                pred,
                Telemetry(
                    zero, zero, jnp.zeros((), bool), warm=True, repaired=0, cone=0
                ),
            )
        if rep is not None:
            tent0 = jnp.asarray(rep.tent0)
            explored0 = jnp.asarray(rep.explored0)
            backend = self._warm_backend(rep.repaired)
            tent, outer, inner, over = self._run_warm(backend, tent0, explored0)
            if backend is not self.backend and bool(np.any(np.asarray(over))):
                # repair cascade outgrew the capped twin: re-run the
                # same warm state full-width (answers never depend on
                # the cap — it only moves time), and escalate the cap
                # floor so this workload's later repairs fit first try
                self._twin_cap_floor = min(backend.cap * 4, self.graph.n_nodes)
                tent, outer, inner, over = self._run_warm(
                    self.backend, tent0, explored0
                )
            tel = Telemetry(
                outer, inner, over, warm=True, repaired=rep.repaired, cone=rep.cone
            )
        else:
            tent, outer, inner, over = self._run1(self.backend, src)
            tel = Telemetry(outer, inner, over, warm=False)
        dist, pred = _finish_pred(tent, self.graph, src, self.config)
        self._remember(r.source, dist, pred, over)
        return SingleSourceResult(dist, pred, tel)

    def _remember(self, source, dist, pred, over) -> None:
        self._resident = Resident(
            source=int(source),
            dist=np.asarray(dist, np.int64),
            pred=np.asarray(pred, np.int32),
            w=np.array(np.asarray(self.graph.w), np.int32),
            overflow=bool(np.any(np.asarray(over))),
        )

    # -- landmark residency (repro.landmarks, DESIGN.md §14) -----------------

    def prepare_landmarks(
        self,
        k: int = 4,
        strategy: str = "farthest",
        seed: int = 0,
        *,
        store: Optional[str] = None,
        on_update: str = "recompute",
        build: bool = True,
    ) -> "Plan":
        """Attach landmark residency to the plan: ``k`` landmarks chosen
        by ``strategy`` (``farthest``/``random``), distance tables
        persisted in the fingerprint-keyed ``store`` directory (``None``
        = in-memory), and the ``on_update`` staleness policy for weight
        batches that would invalidate the tables (``recompute`` drops
        and lazily rebuilds them; ``refuse`` rejects the batch with
        ``LandmarkRefused`` before applying it). ``build=False`` defers
        the precompute to the first landmark-mode query (the serving
        tier's lazy per-tenant configuration). Returns ``self``."""
        from repro.landmarks import LandmarkSpec, LandmarkState, require_canonical

        spec = LandmarkSpec(k=k, strategy=strategy, seed=seed,
                            store=store, on_update=on_update)
        self._landmarks = LandmarkState(spec, self.config.delta)
        if build:
            # deferred builds re-check at the first landmark-mode query
            # (solve_p2p), so a server-wide landmarks knob cannot break
            # a non-canonical tenant that never asks for these modes
            require_canonical(self.graph)
            self._landmarks.ensure_tables(self.graph)
        if self._demoted is not None:
            self._demoted._landmarks = self._landmarks
        return self

    def _landmark_state(self):
        """The plan's landmark residency, created with the default spec
        on first use (a landmark-mode query against an unprepared plan
        still works — it just pays the table build lazily)."""
        if self._landmarks is None:
            from repro.landmarks import LandmarkSpec, LandmarkState

            self._landmarks = LandmarkState(LandmarkSpec(), self.config.delta)
        return self._landmarks

    @property
    def landmark_tables(self):
        """The resident ``LandmarkTables``, or ``None`` when unprepared,
        not yet built, or invalidated by a weight update."""
        return None if self._landmarks is None else self._landmarks.tables

    def explain(self) -> dict:
        """Plan provenance for logs/telemetry: the resolved operating
        point plus the tuning record (if any) it came from.
        ``kernel_path`` says how the strategy's Pallas kernels run:
        'compiled', 'interpret', 'twin', or 'xla' for a strategy
        without kernels (core.backends ``kernel_path``). ``edge_split``
        gives an ``edge`` plan's light and heavy edge counts and the
        capacity its halves are padded to (``EdgeBackend.edge_split``);
        None for other strategies."""
        cfg = self.config
        split = getattr(self.backend, "edge_split", None)
        return {
            "delta": cfg.delta,
            "strategy": cfg.strategy,
            "kernel_path": self.backend.kernel_path,
            "edge_split": None if split is None else split(),
            "policy": cfg.policy,
            "pred_mode": cfg.pred_mode,
            "frontier_cap": cfg.frontier_cap,
            "n_shards": cfg.n_shards,
            "p2p_mode": cfg.p2p_mode,
            "landmarks": (
                None if self.landmark_tables is None
                else self.landmark_tables.k
            ),
            "tuning_source": None if self.record is None else self.record.source,
            "fallback_taken": self._demoted is not None,
            "resident_source": (
                None if self._resident is None else self._resident.source
            ),
        }

    def lower(self, query) -> jax.stages.Lowered:
        """The lowered driver program that answers a ``SingleSource`` or
        ``MultiSource`` query (``MultiSource`` is also the server's lane
        batch), without running it — to inspect what the device will
        run, e.g. whether a Pallas kernel (``tpu_custom_call``) is in
        it."""
        if isinstance(query, SingleSource):
            run, arg = self._run1, jnp.asarray(query.source, jnp.int32)
        elif isinstance(query, MultiSource):
            run, arg = self._run_many, jnp.asarray(query.sources, jnp.int32)
        else:
            raise TypeError(f"cannot lower {type(query).__name__!r}")
        return run.func.lower(self.backend, arg, **run.keywords)

    # -- query dispatch ------------------------------------------------------

    def _dispatch(self, query: Query) -> Result:
        if isinstance(query, SingleSource):
            return self._single(query)
        if isinstance(query, MultiSource):
            return self._multi(query)
        if isinstance(query, PointToPoint):
            return self._point_to_point(query)
        if isinstance(query, BoundedRadius):
            return self._bounded(query)
        if isinstance(query, ManyToMany):
            return self._many_to_many(query)
        raise TypeError(f"unknown query kind {type(query).__name__!r}")

    def _single(self, q: SingleSource) -> SingleSourceResult:
        src = jnp.asarray(
            _check_vertex("source", q.source, self.graph.n_nodes), jnp.int32
        )
        tent, outer, inner, over = self._run1(self.backend, src)
        dist, pred = _finish_pred(tent, self.graph, src, self.config)
        self._remember(q.source, dist, pred, over)  # dynamic residency
        return SingleSourceResult(dist, pred, Telemetry(outer, inner, over))

    def _multi(self, q: MultiSource) -> MultiSourceResult:
        host = np.asarray(q.sources, np.int64)
        if host.ndim != 1:
            raise ValueError("sources must be a 1-D array of vertex ids")
        _check_vertices("sources", host, self.graph.n_nodes)
        srcs = jnp.asarray(host, jnp.int32)
        tent, outer, inner, over = self._run_many(self.backend, srcs)
        dist, pred = _finish_pred_many(tent, self.graph, srcs, self.config)
        return MultiSourceResult(dist, pred, Telemetry(outer, inner, over))

    def _point_to_point(self, q: PointToPoint) -> PointToPointResult:
        n = self.graph.n_nodes
        src = jnp.asarray(_check_vertex("source", q.source, n), jnp.int32)
        tgt = jnp.asarray(_check_vertex("target", q.target, n), jnp.int32)
        mode = q.mode if q.mode is not None else self.config.p2p_mode
        if mode != "early_exit":
            return self._p2p_landmark(q, mode)
        tent, outer, inner, over = self._run_p2p(self.backend, src, tgt)
        # every vertex on a shortest source->target path is settled at
        # early exit (its bucket precedes the target's), so the partial
        # predecessor state is exact along the returned path
        dist, pred = _finish_pred(tent, self.graph, src, self.config)
        distance = int(np.asarray(dist)[int(q.target)])
        path = None
        if distance < int(INF32) and self.config.pred_mode != "none":
            path = extract_path(
                np.asarray(pred), int(q.source), int(q.target), self.graph.n_nodes
            )
        return PointToPointResult(distance, path, Telemetry(outer, inner, over))

    def _p2p_landmark(self, q: PointToPoint, mode: str) -> PointToPointResult:
        """Goal-directed point-to-point (repro.landmarks): the landmark
        state solves over a reduced / doubled graph and hands back
        original-space predecessor trees; the distance is bitwise the
        unidirectional answer (tests/test_landmarks.py pins this), and
        the path goes through the same cycle-guarded extractors as every
        other query."""
        if self.config.policy != "delta":
            raise ValueError(
                "landmark p2p modes (alt/bidirectional) run the bucket "
                "loop's all-light drivers and are delta-only; "
                f"policy={self.config.policy!r} plans answer "
                "PointToPoint via mode='early_exit'"
            )
        lm = self._landmark_state()
        want_pred = self.config.pred_mode != "none"
        r = lm.solve_p2p(self.graph, q.source, q.target, mode,
                         want_pred=want_pred)
        tel = Telemetry(np.int32(r.outer), np.int32(r.inner),
                        np.bool_(r.overflow))
        if r.distance >= int(INF32) or not want_pred:
            return PointToPointResult(r.distance, None, tel)
        n = self.graph.n_nodes
        if r.pred_b is None:
            path = extract_path(np.asarray(r.pred_f), int(q.source),
                                int(q.target), n)
        else:
            path = stitch_bidirectional_path(
                np.asarray(r.pred_f), np.asarray(r.pred_b),
                int(q.source), int(q.target), r.meet, n)
        return PointToPointResult(r.distance, path, tel)

    def _bounded(self, q: BoundedRadius) -> BoundedRadiusResult:
        radius = int(q.radius)
        if not 0 <= radius < int(INF32):
            raise ValueError(f"radius must be in [0, INF32), got {radius}")
        src = jnp.asarray(
            _check_vertex("source", q.source, self.graph.n_nodes), jnp.int32
        )
        r_arr = jnp.asarray(radius, jnp.int32)
        tent, outer, inner, over = self._run_bounded(self.backend, src, r_arr)
        dist, pred = _finish_pred(tent, self.graph, src, self.config)
        # all buckets <= radius // delta were processed, so every vertex
        # with true distance <= radius is settled; the rest are filtered
        # to the unreachable sentinels (their tent values are bounds,
        # not answers)
        within = dist <= radius
        dist = jnp.where(within, dist, jnp.int32(INF32))
        pred = jnp.where(within, pred, jnp.int32(-1))
        return BoundedRadiusResult(dist, pred, radius, Telemetry(outer, inner, over))

    def _many_to_many(self, q: ManyToMany) -> ManyToManyResult:
        n = self.graph.n_nodes
        sources = [_check_vertex("source", s, n) for s in q.sources]
        targets = np.asarray([int(t) for t in q.targets], np.int64)
        if not sources or targets.size == 0:
            raise ValueError("ManyToMany needs non-empty sources and targets")
        _check_vertices("targets", targets, n)
        tile = int(q.tile) if q.tile is not None else min(len(sources), 8)
        if tile < 1:
            raise ValueError(f"tile must be >= 1, got {tile}")
        matrix = np.full((len(sources), len(targets)), int(INF32), np.int64)
        buckets, inner_total, over_any = 0, 0, False
        for lo in range(0, len(sources), tile):
            chunk = sources[lo : lo + tile]
            # short tiles repeat the last source so every tile runs the
            # same compiled shape (the padded lanes are discarded)
            padded = chunk + [chunk[-1]] * (tile - len(chunk))
            srcs = jnp.asarray(padded, jnp.int32)
            tent, outer, inner, over = self._run_many(self.backend, srcs)
            d = np.asarray(dist_of(tent, self._packed))
            matrix[lo : lo + len(chunk)] = d[: len(chunk)][:, targets]
            buckets = max(buckets, int(np.max(np.asarray(outer))))
            inner_total += int(np.sum(np.asarray(inner)))
            over_any = over_any or bool(np.any(np.asarray(over)))
        tel = Telemetry(np.int32(buckets), np.int32(inner_total), np.bool_(over_any))
        return ManyToManyResult(matrix, tel)


class Engine:
    """Façade entry point: holds the graph plus the tuning inputs, and
    mints ``Plan``s. ``config`` is a concrete ``DeltaConfig`` (used
    as-is, or as the tuning *base* — its non-searched fields carry into
    the resolved plan) or ``None``; ``tuning`` is the one resolution
    knob: ``None`` (concrete config as-is), ``"auto"`` (zero-measurement
    estimator), ``"measure"`` (measured search), a cache path, or a
    ``Tuning(measure=..., cache=...)``. ``Engine(graph)`` with neither
    defaults to ``tuning="auto"``. The pre-redesign spellings —
    ``config="auto"``, ``tune=True``, ``tune_cache=path`` — survive as
    deprecated shims mapping onto exactly those semantics.

    >>> import jax.numpy as jnp
    >>> from repro.api import Engine, PointToPoint
    >>> from repro.core import DeltaConfig
    >>> from repro.graphs.structures import COOGraph
    >>> g = COOGraph(jnp.array([0, 1, 0], jnp.int32),
    ...              jnp.array([1, 2, 2], jnp.int32),
    ...              jnp.array([1, 1, 5], jnp.int32), 3)
    >>> plan = Engine(g, DeltaConfig(delta=2, pred_mode="argmin")).plan()
    >>> res = plan.solve(PointToPoint(0, 2))
    >>> (res.distance, res.path)
    (2, [0, 1, 2])
    """

    def __init__(
        self,
        graph: COOGraph,
        config: Union[DeltaConfig, str, None] = None,
        *,
        free_mask=None,
        tuning: Union[Tuning, str, None] = None,
        tune: bool = False,
        tune_cache: Optional[str] = None,
    ):
        if isinstance(config, str):
            if config != "auto":
                raise ValueError(
                    f"unknown config string {config!r} (did you mean "
                    "'auto' or a DeltaConfig?)"
                )
            warnings.warn(
                "config='auto' is deprecated: use Engine(graph, "
                "tuning='auto') (or just Engine(graph))",
                DeprecationWarning,
                stacklevel=2,
            )
            config = None
            if tuning is None and not (tune or tune_cache is not None):
                tuning = "auto"
        if tune or tune_cache is not None:
            warnings.warn(
                "tune=/tune_cache= are deprecated: use tuning="
                "Tuning(measure=..., cache=...)",
                DeprecationWarning,
                stacklevel=2,
            )
            if tuning is None:
                tuning = Tuning(measure=bool(tune), cache=tune_cache)
        if config is None and tuning is None:
            tuning = "auto"  # Engine(graph) keeps its auto-resolve default
        self.graph = graph
        self.free_mask = free_mask
        self._config = config
        self._tuning = _normalize_tuning(tuning)

    def plan(
        self,
        *,
        sources: Optional[Sequence[int]] = None,
        fallback: bool = False,
    ) -> Plan:
        """Resolve the operating point once and return the compiled
        ``Plan``. ``sources`` are the vertices the caller will actually
        solve from: a tuning-chosen ``frontier_cap`` is validated
        against exactly those (one shared ``build_safe_solver`` path)
        and dropped on overflow; ``sources=None`` — a plan that cannot
        know its future queries — drops a tuned cap outright and can
        instead serve with ``fallback=True`` (per-query overflow
        re-solve, the ``SSSPServer`` configuration)."""
        cfg, record = self._resolve(sources)
        return Plan(
            self.graph,
            cfg,
            free_mask=self.free_mask,
            record=record,
            fallback=fallback,
            radii_store=self._radii_store_path(),
        )

    def _radii_store_path(self) -> Optional[str]:
        """Radius-stepping preprocessing lives beside the tuner cache
        (``<cache>.radii/``) whenever the engine has a persistent cache;
        engines without one keep radii in memory."""
        if self._tuning is not None and self._tuning.cache:
            return f"{self._tuning.cache}.radii"
        return None

    def _resolve(self, sources):
        if self._tuning is None:
            return self._config, None  # concrete config, no tuning: as-is
        from repro.tune import resolve_record  # lazy: tune builds on core/api

        base = DeltaConfig() if self._config is None else self._config
        return resolve_record(
            self.graph,
            base,
            free_mask=self.free_mask,
            cache_path=self._tuning.cache,
            measure=self._tuning.measure,
            sources=sources,
        )


__all__ = ["Engine", "LandmarkRefused", "Plan", "Tuning", "UpdateRefused"]
