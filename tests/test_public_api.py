"""API-stability snapshot (DESIGN.md §10): the exported surface of the
Query/Plan façade (``repro.api``) and the deprecated aliases it
subsumes are pinned here, so a refactor cannot silently drop an entry
point — the failure mode that let the public surface fracture into four
overlapping entry points in the first place. Additions are fine (extend
the snapshot); removals/renames must be deliberate."""
import inspect

import repro.api as api
import repro.core as core
import repro.serve as serve
import repro.tune as tune

# the façade surface: the one public entry point set
API_EXPORTS = {
    "BoundedRadius",
    "BoundedRadiusResult",
    "Engine",
    "ManyToMany",
    "ManyToManyResult",
    "MultiSource",
    "MultiSourceResult",
    "Plan",
    "PointToPoint",
    "PointToPointResult",
    "Query",
    "Result",
    "SingleSource",
    "SingleSourceResult",
    "Telemetry",
    "Tuning",
    "UpdateBatch",
    "UpdateRefused",
    "LandmarkRefused",
    "extract_path",
    "stitch_bidirectional_path",
}

# the async serving tier (DESIGN.md §13)
SERVE_EXPORTS = {"Server", "Ticket", "RequestRejected", "RequestTrace",
                 "UpdateApplied"}

# deprecated aliases: the pre-façade entry points kept as thin shims
# under the bitwise-parity contract (tests/test_api_queries.py)
CORE_DEPRECATED = {"DeltaSteppingSolver", "delta_stepping"}
SERVE_DEPRECATED = {"SSSPServer", "SSSPQuery"}

# the tuning surface the façade resolves through
TUNE_REQUIRED = {"resolve_record", "resolve_config", "build_safe_solver",
                 "TuningRecord", "TuningCache", "tune", "tune_p2p"}


def test_api_export_snapshot():
    assert set(api.__all__) == API_EXPORTS
    for name in api.__all__:
        assert hasattr(api, name), name


def test_serve_export_snapshot():
    for name in SERVE_EXPORTS:
        assert name in serve.__all__, name
        assert hasattr(serve, name), name


def test_server_surface():
    """The serving tier's load-bearing signatures (DESIGN.md §13)."""
    assert list(inspect.signature(serve.Server.__init__).parameters) == [
        "self", "graphs", "config", "tuning", "lane_width", "max_resident",
        "max_queue", "clock", "landmarks"]
    assert list(inspect.signature(serve.Server.submit).parameters) == [
        "self", "query", "graph", "deadline"]
    assert list(inspect.signature(serve.Server.admit).parameters) == [
        "self", "name", "graph", "config", "free_mask"]
    for attr in ("submit", "admit", "plan", "stats", "pump", "drain",
                 "start", "close"):
        assert hasattr(serve.Server, attr), attr
    for attr in ("result", "done", "exception"):
        assert hasattr(serve.Ticket, attr), attr
    assert [f for f in serve.RequestTrace.__dataclass_fields__] == [
        "tenant", "kind", "t_submit", "t_batch", "t_solve", "t_done",
        "batch_occupancy", "shed", "request_id", "batch_id", "t_ready",
        "d2h_bytes"]


def test_deprecated_aliases_still_exported():
    for name in CORE_DEPRECATED:
        assert name in core.__all__, name
        assert hasattr(core, name), name
    for name in SERVE_DEPRECATED:
        assert name in serve.__all__, name
        assert hasattr(serve, name), name
    for name in TUNE_REQUIRED:
        assert name in tune.__all__, name
        assert hasattr(tune, name), name


def test_deprecated_signatures_frozen():
    """The shim signatures are the parity contract: old call sites must
    keep working verbatim."""
    assert list(inspect.signature(
        core.DeltaSteppingSolver.__init__).parameters) == [
        "self", "graph", "config", "free_mask", "tune_cache"]
    assert list(inspect.signature(
        core.delta_stepping).parameters) == ["graph", "source", "config"]
    assert list(inspect.signature(
        serve.SSSPServer.__init__).parameters) == [
        "self", "graph", "config", "batch_size", "free_mask", "tune",
        "tune_cache"]
    # solve/solve_many keep returning the legacy SSSPResult tuple
    assert core.SSSPResult._fields == (
        "dist", "pred", "outer_iters", "inner_iters", "overflow")


def test_engine_and_plan_surface():
    """The façade's own load-bearing methods/attributes."""
    import jax.numpy as jnp
    from repro.graphs.structures import COOGraph

    assert list(inspect.signature(api.Engine.__init__).parameters) == [
        "self", "graph", "config", "free_mask", "tuning", "tune",
        "tune_cache"]
    assert [f for f in api.Tuning.__dataclass_fields__] == [
        "measure", "cache"]
    assert list(inspect.signature(api.Engine.plan).parameters) == [
        "self", "sources", "fallback"]
    assert list(inspect.signature(api.Plan.solve).parameters) == [
        "self", "query"]
    g = COOGraph(jnp.array([0], jnp.int32), jnp.array([1], jnp.int32),
                 jnp.array([3], jnp.int32), 2)
    plan = api.Engine(g, core.DeltaConfig(delta=4)).plan()
    for attr in ("config", "graph", "backend", "record", "solve",
                 "explain", "update", "resolve", "prepare_landmarks",
                 "landmark_tables"):
        assert hasattr(plan, attr), attr
    assert list(inspect.signature(api.Plan.update).parameters) == [
        "self", "edge_ids", "new_weights"]
    assert list(inspect.signature(api.Plan.resolve).parameters) == [
        "self", "warm"]
    assert plan.record is None              # no tuning inputs, no record
    assert isinstance(plan.explain(), dict)


def test_query_algebra_fields():
    """Query constructors are the wire format of the façade — pin their
    field names."""
    assert [f for f in api.SingleSource.__dataclass_fields__] == ["source"]
    assert [f for f in api.MultiSource.__dataclass_fields__] == ["sources"]
    assert [f for f in api.PointToPoint.__dataclass_fields__] == [
        "source", "target", "mode"]
    assert [f for f in api.BoundedRadius.__dataclass_fields__] == [
        "source", "radius"]
    assert [f for f in api.ManyToMany.__dataclass_fields__] == [
        "sources", "targets", "tile"]
    assert [f for f in api.UpdateBatch.__dataclass_fields__] == [
        "edge_ids", "new_weights", "warm"]
    assert [f for f in api.Telemetry.__dataclass_fields__] == [
        "buckets", "inner_iters", "overflow", "fallback", "warm",
        "repaired", "cone"]
