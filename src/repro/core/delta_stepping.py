"""Single-device Δ-stepping SSSP engine (paper Alg. 1/2, DESIGN.md §2–3).

The paper's shared-memory mechanisms map onto JAX dataflow:

* dense bucket array (C1)   → ``bucket_id = tent // Δ`` recomputed by a
  full vector scan each inner iteration; the frontier is a boolean mask.
* CAS minimum loop (C2)     → ``tent.at[dst].min(candidates)`` scatter-min.
* 64-bit (cost, pred) packing (C3) → ``pred_mode='packed'`` scatter-mins
  one int64 word per vertex; ``pred_mode='argmin'`` is the 32-bit
  two-pass TPU-idiomatic equivalent (post-hoc tree recovery).
* private request sets + dup work (C4) → relaxations are emitted without
  dedup and filtered early with ``cand < tent[dst]`` before the scatter.
* read/write decoupling (C5) → gather phase and scatter phase are
  separate XLA ops by construction.

One generic outer/inner loop driver hosts every relaxation strategy via
the ``RelaxBackend`` protocol (core.backends): ``edge`` (edge-centric
|E| sweeps), ``ell`` (frontier-compacted ELL expansion) and ``pallas``
(the ELL expansion and bucket scan on the Pallas TPU kernels under
``kernels/``; game-map instances use the grid stencil kernel).

Batched multi-source solving (``_run_many_vmapped``) vmaps the driver
over a batch of sources: the carried state (tent / explored / frontier,
bucket index, iteration counters) gains a leading batch axis, the
while-loops run until every lane converges, and converged lanes are
frozen by the batching rule's select — so per-source counters and
results are bitwise identical to per-source single solves.

The public surface of this module is consumed through the Query/Plan
façade (``repro.api``, DESIGN.md §10): ``Plan`` partially applies the
module-level jitted drivers below, and the early-exit query kinds
(point-to-point, bounded radius) are the ``stop``-predicate variants of
the same loop. ``DeltaSteppingSolver`` survives as a deprecated shim.

Weights must be non-negative int32; ``pred_mode='argmin'`` additionally
assumes weights >= 1 (zero-weight ties could close a predecessor cycle;
``packed`` mode is safe for zero weights, see pack.py).
"""
from __future__ import annotations

import dataclasses
import warnings
from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import pack as packing
from repro.core.backends import (
    RelaxBackend,
    dist_of as _dist_of,
    init_tent as _init_tent,
)
from repro.core.policies import POLICIES
from repro.graphs.structures import COOGraph, INF32

_IMAX = jnp.int32(2**31 - 1)

# PointToPoint answer modes (DESIGN.md §14): the classic early exit plus
# the goal-directed landmark modes served by repro.landmarks.
P2P_MODES = ("early_exit", "alt", "bidirectional", "alt_bidirectional")

# Per-side clamp of the bidirectional meeting sums: tent values are
# clipped to 2^30 - 1 before the int32-safe f + b addition. Exact
# whenever finite point-to-point distances stay below 2^30 — a slightly
# tighter form of the engine's existing no-overflow assumption (every
# relaxation computes dist + w in int32).
_MEET_CLIP = jnp.int32(2**30 - 1)


@dataclasses.dataclass(frozen=True)
class DeltaConfig:
    """Configuration of the Δ-stepping engine.

    delta        — bucket width Δ (paper's tuning parameter, Fig. 1).
    strategy     — 'edge' | 'ell' | 'pallas' | 'fused' | 'sharded_edge'
                   | 'sharded_ell' | 'sharded_fused' relaxation backend
                   (see module doc / DESIGN.md §3, §9, §12).
    pred_mode    — 'none' | 'argmin' | 'packed' predecessor tracking.
    frontier_cap — ELL-family ('ell'/'pallas'/'fused') only: static
                   capacity of the compacted frontier (defaults to |V|;
                   smaller saves work if an upper bound on per-bucket
                   frontier size is known — the ``overflow`` result
                   flag reports violations). For 'sharded_ell' /
                   'sharded_fused' the cap is *per shard* (defaults to
                   the owned vertex range, which cannot overflow).
    interpret    — kernel strategies ('pallas'/'fused'/'sharded_fused')
                   off TPU: run the Pallas kernels in the interpreter.
                   Refused on TPU, where kernels always run compiled
                   (backends ``kernel_path``).
    kernel_twin  — 'fused'/'sharded_fused' off TPU: run the bitwise jnp
                   twin of the ``frontier_relax`` kernel instead. Off
                   TPU a kernel strategy needs one of the two; refused
                   on TPU.
    grid_costs   — 'pallas' on game maps: (straight, diagonal) move
                   costs of the occupancy-grid stencil (paper §4).
    n_shards     — 'sharded_*' only: width of the 1-D device mesh the
                   relaxation is partitioned over (None = every local
                   device; DESIGN.md §9).
    p2p_mode     — default answer mode of ``PointToPoint`` queries:
                   'early_exit' (the classic settled-bucket exit),
                   'alt' (goal-directed landmark potentials),
                   'bidirectional' (forward+backward meeting rule) or
                   'alt_bidirectional' (both; repro.landmarks,
                   DESIGN.md §14). Queries can override per-call.
    policy       — frontier-selection policy (DESIGN.md §15): 'delta'
                   (the paper's bucket loop), 'rho' (ρ-stepping: pop
                   the ρ nearest pending vertices per round) or
                   'radius' (radius-stepping: per-vertex precomputed
                   step radii). Every policy runs over the same
                   relaxation backends and is bitwise-pinned to the
                   Dijkstra oracle; 'delta' keeps the classic loop
                   bit-for-bit unchanged. The grid-stencil game-map
                   path ('pallas' + free_mask) and the landmark p2p
                   modes are delta-only.
    rho          — 'rho' only: batch size ρ (None = heuristic
                   ``policies.default_rho``).
    radius_k     — 'radius' only: r(v) is the k-th smallest outgoing
                   edge weight (see policies.compute_radii).
    """

    delta: int = 10
    strategy: str = "edge"
    pred_mode: str = "argmin"
    frontier_cap: Optional[int] = None
    interpret: bool = False
    kernel_twin: bool = False
    grid_costs: Tuple[int, int] = (10, 14)
    n_shards: Optional[int] = None
    p2p_mode: str = "early_exit"
    policy: str = "delta"
    rho: Optional[int] = None
    radius_k: int = 4

    def __post_init__(self):
        if self.p2p_mode not in P2P_MODES:
            raise ValueError(f"unknown p2p_mode {self.p2p_mode!r}")
        if self.strategy not in ("edge", "ell", "pallas", "fused",
                                 "sharded_edge", "sharded_ell",
                                 "sharded_fused"):
            raise ValueError(f"unknown strategy {self.strategy!r}")
        if self.pred_mode not in ("none", "argmin", "packed"):
            raise ValueError(f"unknown pred_mode {self.pred_mode!r}")
        if self.delta < 1:
            raise ValueError("delta must be >= 1")
        if self.n_shards is not None and self.n_shards < 1:
            raise ValueError("n_shards must be >= 1")
        if self.policy not in POLICIES:
            raise ValueError(f"unknown policy {self.policy!r}")
        if self.rho is not None and self.rho < 1:
            raise ValueError("rho must be >= 1")
        if self.radius_k < 1:
            raise ValueError("radius_k must be >= 1")


class SSSPResult(NamedTuple):
    """Solve result; ``solve_many`` returns the same tuple with a leading
    batch axis on every field."""

    dist: jax.Array          # int32[n], INF32 = unreachable
    pred: jax.Array          # int32[n], -1 = source/unreachable
    outer_iters: jax.Array   # int32: number of buckets processed
    inner_iters: jax.Array   # int32: total light-phase sweeps
    overflow: jax.Array      # bool: compacted frontier capacity exceeded


def _require_x64():
    if jnp.zeros((), jnp.int64).dtype != jnp.int64:
        raise RuntimeError(
            "pred_mode='packed' packs (dist, pred) into int64 and requires "
            "x64 (wrap the call in repro.compat.enable_x64())."
        )


# ---------------------------------------------------------------------------
# the unified loop driver — generic over RelaxBackend and vmap-batchable
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("n", "packed"))
def _run_one(backend: RelaxBackend, source, *, n: int, packed: bool):
    """Jitted single-source driver. Module-level so the compile cache is
    shared across ``DeltaSteppingSolver`` instances: the backend is a
    pytree *argument* whose static fields (strategy class, Δ, caps) are
    part of the cache key, so same-shaped solvers never recompile."""
    return _run_backend(backend, source, n=n, packed=packed)


@partial(jax.jit, static_argnames=("n", "packed"))
def _run_many_vmapped(backend: RelaxBackend, sources, *, n: int,
                      packed: bool):
    """Jitted batched multi-source driver (vmapped state)."""
    return jax.vmap(
        lambda s: _run_backend(backend, s, n=n, packed=packed))(sources)


@partial(jax.jit, static_argnames=("n", "packed"))
def _run_many_seq(backend: RelaxBackend, sources, *, n: int, packed: bool):
    """Batched driver for backends without a batching rule
    (``pallas_call`` with scalar-prefetch grids): in-program lax.map."""
    return lax.map(
        lambda s: _run_backend(backend, s, n=n, packed=packed), sources)


def _pending_min(d, explored):
    """Minimum tentative distance over *pending* vertices — those whose
    tent improved since their edges were last relaxed (``tent <
    explored``; an undiscovered vertex has tent == explored == INF and
    is excluded). On an all-light backend every future tent assignment
    derives from relaxing a pending vertex, so every future value is
    >= this bound — the Dijkstra priority-queue minimum, recovered from
    the Δ-stepping state."""
    return jnp.where(d < explored, d, INF32).min()


@partial(jax.jit, static_argnames=("n", "packed", "all_light"))
def _run_one_p2p(backend: RelaxBackend, source, target, *, n: int,
                 packed: bool, all_light: bool = False):
    """Jitted point-to-point driver with early exit (Kainer & Träff
    2019, DESIGN.md §10): when the outer loop advances past bucket i,
    every vertex whose tentative distance lies in a bucket <= i is
    settled — and the next-bucket scan is a global min over *all*
    finite tent values, so ``tent[target] // Δ < next_bucket`` proves
    the target's bucket was already processed and its distance is
    final. ``target`` is a traced argument (no recompile per target).

    ``all_light=True`` (the landmark ALT path, DESIGN.md §14) adds a
    mid-bucket exit: once ``tent[target] <= min pending tent``, no
    future relaxation can improve the target (weights >= 0, so every
    future value is >= the pending minimum) — essential under tight
    potentials, where the whole corridor collapses into bucket 0 and
    the between-buckets test above never gets a chance to fire. Sound
    only when every relaxed vertex has *all* its edges swept at once
    (no deferred heavy phase), hence the all-light gate."""
    delta = backend.delta

    def stop(tent, explored, nxt):
        d_t = _dist_of(tent, packed)[target]
        return (d_t < INF32) & ((d_t // delta) < nxt)

    inner_stop = None
    if all_light:
        def inner_stop(tent, explored):
            d = _dist_of(tent, packed)
            return (d[target] < INF32) & (d[target] <= _pending_min(
                d, explored))

    return _run_backend(backend, source, n=n, packed=packed, stop=stop,
                        inner_stop=inner_stop)


@partial(jax.jit, static_argnames=("n", "packed"))
def _run_one_warm(backend: RelaxBackend, tent0, explored0, *, n: int,
                  packed: bool):
    """Jitted warm-start driver (repro.dynamic, DESIGN.md §11): the same
    outer/inner bucket loop, entered with a *repaired* state instead of
    the all-INF cold one. ``tent0`` are upper-bound tent words (dist, or
    packed (dist, pred)); ``explored0`` marks the tent value each vertex
    last relaxed its edges at (its old settled distance), so exactly the
    vertices whose tent was improved or reset by the repair satisfy
    ``tent < explored`` and re-enter their buckets — Δ-stepping's own
    frontier rule bounds the re-relaxation to the repair cone, and the
    unsettled-only next-bucket scan skips every bucket the repair never
    touched."""
    return _run_backend(backend, None, n=n, packed=packed,
                        init=(tent0, explored0))


@partial(jax.jit, static_argnames=("n", "packed", "all_light"))
def _run_one_bidir(backend: RelaxBackend, tent0, explored0, *, n: int,
                   packed: bool, all_light: bool = False):
    """Jitted bidirectional point-to-point driver (repro.landmarks,
    DESIGN.md §14). ``backend`` relaxes the disjoint union of the graph
    with its reversed copy (``graphs.union_with_reverse``; ``n`` is the
    union size ``2 * half``); ``tent0`` seeds *two* searches through the
    warm-init hook — the source in the forward half and the target's
    twin in the reversed half — so one lockstep bucket loop advances
    both searches.

    Meeting rule, layered on the ``_run_backend`` stop hooks: let
    μ = min_v (tent[v] + tent[v + half]) over the half vertices — an
    upper bound on dist(s, t) that becomes exact once some vertex on a
    shortest path has exact tents on both sides.

    * Generic backends stop between buckets when 2·nxt·Δ >= μ: every
      unsettled vertex of either half has tent >= nxt·Δ, so a
      hypothetical shorter path would need a vertex settled forward
      whose successor y has forward distance >= nxt·Δ, forcing y's
      backward distance below nxt·Δ — i.e. y settled backward and μ
      already counts the shorter sum.
    * ``all_light=True`` (the landmark path) sharpens this to the
      classic bidirectional-Dijkstra rule μ <= L_f + L_b, where L_f/L_b
      are the per-half *pending* minimums, checked mid-bucket too —
      under tight ALT potentials both searches live entirely in bucket
      0 and the bucket-granular test above never fires before the full
      closure. Sound because all-light relaxation sweeps every edge of
      a vertex the moment it leaves the pending set (DESIGN.md §14 has
      the full argument).

    The returned tent carries both half solutions; the caller extracts
    μ = dist(s, t) and the meeting vertex host-side."""
    half = n // 2
    delta = backend.delta

    def _mu(d):
        f, b = d[:half], d[half:]
        fin = (f < INF32) & (b < INF32)
        sums = jnp.where(
            fin, jnp.minimum(f, _MEET_CLIP) + jnp.minimum(b, _MEET_CLIP),
            INF32)
        return sums.min()

    inner_stop = None
    if all_light:
        def meet(tent, explored):
            d = _dist_of(tent, packed)
            lf = _pending_min(d[:half], explored[:half])
            lb = _pending_min(d[half:], explored[half:])
            # clamping only lowers the threshold (never a premature
            # stop); an exhausted side (L = INF -> clip) is genuinely
            # final — its half of every sum is exact
            bound = (jnp.minimum(lf, _MEET_CLIP)
                     + jnp.minimum(lb, _MEET_CLIP))
            mu = _mu(d)
            return (mu < INF32) & (mu <= bound)

        stop = lambda tent, explored, nxt: meet(tent, explored)  # noqa: E731
        inner_stop = meet
    else:
        def stop(tent, explored, nxt):
            mu = _mu(_dist_of(tent, packed))
            # 2·nxt·Δ <= 2·max finite tent < 2^32: an int32 wrap can
            # only go negative, which keeps the loop running (still
            # exact, just no early exit) — never a premature stop
            return (mu < INF32) & (2 * nxt * delta >= mu)

    return _run_backend(backend, None, n=n, packed=packed, stop=stop,
                        init=(tent0, explored0), inner_stop=inner_stop)


@partial(jax.jit, static_argnames=("n", "packed"))
def _run_one_bounded(backend: RelaxBackend, source, radius, *, n: int,
                     packed: bool):
    """Jitted bounded-radius driver: stop at the first bucket past
    ``radius // Δ`` — every vertex with true distance <= radius lives
    in a bucket <= radius // Δ and is settled by then; tent values
    beyond are upper bounds, not answers (the caller filters them)."""
    delta = backend.delta

    def stop(tent, explored, nxt):
        return nxt > radius // delta

    return _run_backend(backend, source, n=n, packed=packed, stop=stop)


def _run_backend(backend: RelaxBackend, source, *, n: int, packed: bool,
                 stop=None, init=None, inner_stop=None):
    """Outer/inner Δ-stepping loop (paper Alg. 1) over one backend.
    Returns ``(tent, outer_iters, inner_iters, overflow)``. ``stop``
    (trace-time constant) is an optional early-exit predicate
    ``(tent, explored, next_bucket) -> bool`` checked between buckets —
    the hook the point-to-point and bounded-radius drivers hang off;
    ``inner_stop`` is a ``(tent, explored) -> bool`` predicate checked
    before every light sweep for exits that must fire *inside* a
    bucket's closure (the all-light landmark drivers; its soundness
    burden is the caller's). ``None`` for both keeps the full-solve
    loop bit-for-bit unchanged. ``init`` is an optional warm
    ``(tent0, explored0)`` state (the repro.dynamic repair path,
    DESIGN.md §11); ``None`` is the cold all-INF start."""
    if init is None:
        tent0 = _init_tent(n, source, packed)
        explored0 = jnp.full((n,), INF32, jnp.int32)
    else:
        tent0, explored0 = init

    def scan(tent, explored, i):
        return backend.scan(_dist_of(tent, packed), explored, i)

    @jax.named_scope("light_phase")
    def light_phase(tent, explored, i, inner, over):
        in_s0 = jnp.zeros((n,), bool)
        f0, go0, _ = scan(tent, explored, i)

        def cond(c):
            go = c[6]
            if inner_stop is not None:
                go = go & jnp.logical_not(inner_stop(c[0], c[1]))
            return go

        def body(c):
            tent, explored, in_s, inner, over, f, _ = c
            d = _dist_of(tent, packed)
            explored = jnp.where(f, d, explored)   # paper: move into S
            in_s = in_s | f
            tent, o = backend.sweep(tent, f, i, light=True, packed=packed)
            f, go, _ = scan(tent, explored, i)
            return (tent, explored, in_s, inner + 1, over | o, f, go)

        tent, explored, in_s, inner, over, _, _ = lax.while_loop(
            cond, body, (tent, explored, in_s0, inner, over, f0, go0))
        return tent, explored, in_s, inner, over

    # fused light phase (DESIGN.md §12): backends implementing the
    # fused protocol run scan + compaction + row gather as ONE step
    # (``fused_iter``), so the loop needs no full-width frontier mask in
    # its carry at all. The classic loop primes with a scan and re-scans
    # inside the body; here each iteration is scan-then-relax *atomic*,
    # which appends exactly one vacuous trailing iteration (the scan
    # that finds the bucket empty; all its updates are sentinel no-ops).
    # Counting ``inner += any`` instead of ``inner += 1`` makes the
    # counters — and the whole state trajectory — bitwise those of the
    # classic loop (same op sequence on the same states).
    fused = getattr(backend, "supports_fused_light", False)

    @jax.named_scope("light_phase")
    def light_phase_fused(tent, explored, i, inner, over):
        in_s0 = jnp.zeros((n,), bool)

        def cond(c):
            go = c[5]
            if inner_stop is not None:
                go = go & jnp.logical_not(inner_stop(c[0], c[1]))
            return go

        def body(c):
            tent, explored, in_s, inner, over, _ = c
            tent, explored, in_s, any_, o = backend.fused_iter(
                tent, explored, in_s, i, packed=packed)
            return (tent, explored, in_s, inner + any_.astype(jnp.int32),
                    over | o, any_)

        tent, explored, in_s, inner, over, _ = lax.while_loop(
            cond, body,
            (tent, explored, in_s0, inner, over, jnp.ones((), bool)))
        return tent, explored, in_s, inner, over

    def outer_body(c):
        tent, explored, i, outer, inner, over = c
        phase = light_phase_fused if fused else light_phase
        tent, explored, in_s, inner, over = phase(
            tent, explored, i, inner, over)
        # heavy pass from S (paper Alg. 1 lines 19-20)
        with jax.named_scope("heavy_sweep"):
            tent, o = backend.sweep(tent, in_s, i, light=False,
                                    packed=packed)
        if fused:
            nxt = backend.fused_next(_dist_of(tent, packed), explored, i)
        else:
            _, _, nxt = scan(tent, explored, i)
        return (tent, explored, nxt, outer + 1, inner, over | o)

    def outer_cond(c):
        go = c[2] < _IMAX
        if stop is not None:
            go = go & jnp.logical_not(stop(c[0], c[1], c[2]))
        return go

    i0 = jnp.zeros((), jnp.int32)  # relax(s, 0) puts the source in B_0
    tent, _, _, outer, inner, over = lax.while_loop(
        outer_cond, outer_body,
        (tent0, explored0, i0, jnp.zeros((), jnp.int32),
         jnp.zeros((), jnp.int32), jnp.zeros((), bool)))
    return tent, outer, inner, over


# ---------------------------------------------------------------------------
# the generic frontier-policy loop (DESIGN.md §15) — rho / radius stepping
# over the very same relaxation backends
# ---------------------------------------------------------------------------

def _run_policy(backend: RelaxBackend, policy, source, *, n: int,
                packed: bool, stop=None, init=None):
    """Round loop generic over a :mod:`repro.core.policies` policy.
    Each round: compute the policy threshold θ from the pending state,
    step the value-closed frontier ``pending & (tent <= θ)`` — mark it
    explored, then sweep its **full** edge set through the unchanged
    backend (light phase then heavy phase; the phase split is Δ-bucket
    machinery the policies reuse purely as an edge partition). Closure
    policies (radius) re-step under the same θ until nothing pending
    remains at or below it.

    Correctness is policy-independent: any round that relaxes all edges
    of a non-empty pending subset permanently settles at least the
    pending-minimum vertex (θ >= the pending minimum for every policy,
    and that vertex's tent is final by the Dijkstra argument), so the
    loop reaches the unique distance fixpoint in <= |V| rounds. Because
    no heavy work is ever deferred across rounds, every future tent
    assignment derives from a currently-pending vertex — which makes
    ``_pending_min`` a sound stop bound here for *every* policy (the
    p2p / bounded drivers below), where the bucket loop needs its
    all-light gate.

    Telemetry: ``outer`` counts policy rounds (the analogue of buckets
    processed), ``inner`` counts relaxation iterations (sweep pairs) —
    same counters, same meanings, comparable across policies.

    ``stop`` is an optional ``(tent, explored) -> bool`` early-exit
    predicate checked between rounds; ``init`` is the warm
    ``(tent0, explored0)`` state (repro.dynamic). The pending rule —
    not anything bucket- or policy-shaped — drives selection, which is
    exactly why the repair path is policy-agnostic (DESIGN.md §15)."""
    if init is None:
        tent0 = _init_tent(n, source, packed)
        explored0 = jnp.full((n,), INF32, jnp.int32)
    else:
        tent0, explored0 = init

    zero_i = jnp.zeros((), jnp.int32)  # dummy bucket id: only the grid
    # stencil backend reads it, and grid plans are delta-only (rejected
    # at Plan construction)

    def step(tent, explored, theta, inner, over):
        d = _dist_of(tent, packed)
        f = (d < explored) & (d <= theta)
        explored = jnp.where(f, d, explored)
        tent, o1 = backend.sweep(tent, f, zero_i, light=True, packed=packed)
        tent, o2 = backend.sweep(tent, f, zero_i, light=False, packed=packed)
        return tent, explored, inner + 1, over | o1 | o2

    if policy.closure:
        def round_body(c):
            tent, explored, outer, inner, over = c
            theta = policy.threshold(_dist_of(tent, packed), explored)

            def icond(ic):
                d = _dist_of(ic[0], packed)
                return ((d < ic[1]) & (d <= theta)).any()

            def ibody(ic):
                return step(ic[0], ic[1], theta, ic[2], ic[3])

            tent, explored, inner, over = lax.while_loop(
                icond, ibody, (tent, explored, inner, over))
            return (tent, explored, outer + 1, inner, over)
    else:
        def round_body(c):
            tent, explored, outer, inner, over = c
            theta = policy.threshold(_dist_of(tent, packed), explored)
            tent, explored, inner, over = step(
                tent, explored, theta, inner, over)
            return (tent, explored, outer + 1, inner, over)

    def round_cond(c):
        d = _dist_of(c[0], packed)
        go = (d < c[1]).any()
        if stop is not None:
            go = go & jnp.logical_not(stop(c[0], c[1]))
        return go

    tent, _, outer, inner, over = lax.while_loop(
        round_cond, round_body,
        (tent0, explored0, jnp.zeros((), jnp.int32),
         jnp.zeros((), jnp.int32), jnp.zeros((), bool)))
    return tent, outer, inner, over


@partial(jax.jit, static_argnames=("n", "packed"))
def _run_policy_one(backend: RelaxBackend, source, *, policy, n: int,
                    packed: bool):
    """Jitted single-source policy driver (the non-delta twin of
    ``_run_one``; the policy is a pytree argument, so its static shape
    — ρ, the policy class — keys the compile cache while radius leaves
    swap freely)."""
    return _run_policy(backend, policy, source, n=n, packed=packed)


@partial(jax.jit, static_argnames=("n", "packed"))
def _run_policy_many_vmapped(backend: RelaxBackend, sources, *, policy,
                             n: int, packed: bool):
    """Jitted batched policy driver (vmapped lanes, bitwise equal to
    per-source single solves — same argument as ``_run_many_vmapped``)."""
    return jax.vmap(lambda s: _run_policy(
        backend, policy, s, n=n, packed=packed))(sources)


@partial(jax.jit, static_argnames=("n", "packed"))
def _run_policy_many_seq(backend: RelaxBackend, sources, *, policy,
                         n: int, packed: bool):
    """Batched policy driver for backends without a batching rule."""
    return lax.map(lambda s: _run_policy(
        backend, policy, s, n=n, packed=packed), sources)


@partial(jax.jit, static_argnames=("n", "packed"))
def _run_policy_p2p(backend: RelaxBackend, source, target, *, policy,
                    n: int, packed: bool):
    """Point-to-point early exit under a policy loop: stop once
    ``tent[target] <= min pending tent`` — sound for every policy
    because each policy round sweeps the full edge set of what it
    relaxes (see ``_run_policy``), so all future values are >= the
    pending minimum. This is the policy-loop analogue of the bucket
    driver's ``all_light`` mid-bucket exit."""
    def stop(tent, explored):
        d = _dist_of(tent, packed)
        return (d[target] < INF32) & (d[target] <= _pending_min(d, explored))

    return _run_policy(backend, policy, source, n=n, packed=packed,
                       stop=stop)


@partial(jax.jit, static_argnames=("n", "packed"))
def _run_policy_bounded(backend: RelaxBackend, source, radius, *, policy,
                        n: int, packed: bool):
    """Bounded-radius policy driver: stop once the pending minimum
    exceeds ``radius`` — from then on every future assignment is
    > radius, so all tent values <= radius are final and anything
    beyond is an upper bound the caller filters (same contract as the
    bucket driver's past-the-bucket stop)."""
    def stop(tent, explored):
        return _pending_min(_dist_of(tent, packed), explored) > radius

    return _run_policy(backend, policy, source, n=n, packed=packed,
                       stop=stop)


@partial(jax.jit, static_argnames=("n", "packed"))
def _run_policy_warm(backend: RelaxBackend, tent0, explored0, *, policy,
                     n: int, packed: bool):
    """Warm-start policy driver (repro.dynamic, DESIGN.md §11/§15): the
    policy round loop entered with the repaired state. The repair
    machinery is untouched — it only manufactures ``tent < explored``
    on the repair cone, and the pending rule is what every policy
    selects from, so warm == cold holds per policy by the same unique
    -fixpoint argument as for Δ-stepping."""
    return _run_policy(backend, policy, None, n=n, packed=packed,
                       init=(tent0, explored0))


# ---------------------------------------------------------------------------
# predecessor recovery (two-pass argmin mode)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("n",))
@jax.named_scope("pred_argmin")
def pred_argmin(dist, src, dst, w, source, *, n: int):
    """Recover a shortest-path tree from converged distances: for every
    edge achieving dist[src] + w == dist[dst], scatter-min the source id.
    Deterministic (smallest-id parent wins), matching packed-mode ties."""
    d_src = jnp.take(dist, src, mode="fill", fill_value=INF32)
    d_dst = jnp.take(dist, dst, mode="fill", fill_value=INF32)
    cand = jnp.where(d_src < INF32, d_src, 0) + jnp.where(d_src < INF32, w, 0)
    ok = (d_src < INF32) & (d_dst < INF32) & (cand == d_dst)
    p = jnp.full((n,), _IMAX, jnp.int32).at[dst].min(
        jnp.where(ok, src, _IMAX), mode="drop")
    pred = jnp.where((p < _IMAX) & (dist < INF32), p, -1)
    return pred.at[source].set(-1)


def _finish_pred(tent, coo: COOGraph, source, cfg: DeltaConfig):
    packed = cfg.pred_mode == "packed"
    dist = _dist_of(tent, packed)
    if cfg.pred_mode == "none":
        pred = jnp.full((coo.n_nodes,), -1, jnp.int32)
    elif cfg.pred_mode == "packed":
        pred = packing.unpack_pred(tent)
        pred = jnp.where(dist < INF32, pred, -1).at[source].set(-1)
    else:
        pred = pred_argmin(dist, coo.src, coo.dst, coo.w, source,
                           n=coo.n_nodes)
    return dist, pred


def _finish_pred_many(tent, coo: COOGraph, srcs, cfg: DeltaConfig):
    """Batched twin of :func:`_finish_pred` (leading batch axis on
    ``tent``/``srcs``); shared by the façade's MultiSource dispatch and
    the deprecated ``solve_many`` shim so the two stay bitwise equal."""
    packed = cfg.pred_mode == "packed"
    dist = _dist_of(tent, packed)
    if cfg.pred_mode == "none":
        pred = jnp.full(dist.shape, -1, jnp.int32)
    elif packed:
        pred = packing.unpack_pred(tent)
        pred = jnp.where(dist < INF32, pred, -1)
        pred = pred.at[jnp.arange(srcs.shape[0]), srcs].set(-1)
    else:
        pred = jax.vmap(lambda d, s: pred_argmin(
            d, coo.src, coo.dst, coo.w, s, n=coo.n_nodes))(dist, srcs)
    return dist, pred


# ---------------------------------------------------------------------------
# public API — deprecated shims over the Query/Plan façade (repro.api)
# ---------------------------------------------------------------------------

class DeltaSteppingSolver:
    """**Deprecated** thin shim over the Query/Plan façade — prefer
    ``repro.api.Engine(graph, config).plan()`` (DESIGN.md §10).

    Kept with its original signature under a parity contract: ``solve``
    and ``solve_many`` delegate to ``Plan.solve(SingleSource(...))`` /
    ``Plan.solve(MultiSource(...))``, which run the very same module-
    level jitted drivers and finishers this class used to own, so dist
    and pred (including packed (cost, pred) words) are bitwise
    identical to the pre-façade solver on every backend
    (tests/test_api_queries.py pins this).

    ``free_mask`` (bool[H, W]) marks the game-map graph class: together
    with ``strategy='pallas'`` it routes relaxation to the grid-stencil
    kernel (DESIGN.md §3). ``config="auto"`` consults the tuning
    subsystem (DESIGN.md §7); ``tune_cache`` names the persistent cache
    file to consult."""

    def __init__(self, graph: COOGraph, config: DeltaConfig = DeltaConfig(),
                 *, free_mask=None, tune_cache: Optional[str] = None):
        warnings.warn(
            "DeltaSteppingSolver is deprecated: use repro.api.Engine("
            "graph, config).plan() and the query algebra (DESIGN.md §10)",
            DeprecationWarning, stacklevel=2)
        from repro.api import Engine, Tuning  # lazy: api builds on this
        # legacy semantics, preserved exactly: tune_cache is consulted
        # for config="auto" only — a concrete config a caller pinned is
        # never overwritten by a cached record (Engine would treat it as
        # a tuning base; the old _resolve_auto did not). sources=None:
        # the solver cannot know its future sources, so a tuning-chosen
        # frontier cap is dropped rather than trusted.
        if isinstance(config, str):
            if config != "auto":
                raise ValueError(f"config must be 'auto', got {config!r}")
            engine = Engine(graph, None, free_mask=free_mask,
                            tuning=Tuning(cache=tune_cache))
        else:
            engine = Engine(graph, config, free_mask=free_mask)
        self._plan = engine.plan(sources=None)
        self.config = self._plan.config
        self.graph = graph
        self.backend = self._plan.backend

    @property
    def plan(self):
        """The underlying ``repro.api.Plan`` (the migration path)."""
        return self._plan

    def solve(self, source: int) -> SSSPResult:
        from repro.api import SingleSource
        r = self._plan.solve(SingleSource(source))
        t = r.telemetry
        return SSSPResult(r.dist, r.pred, t.buckets, t.inner_iters,
                          t.overflow)

    def solve_many(self, sources) -> SSSPResult:
        """Batched multi-source solve on one device. Returns an
        ``SSSPResult`` whose fields carry a leading batch axis; every
        lane is bitwise identical to the corresponding ``solve``."""
        from repro.api import MultiSource
        r = self._plan.solve(MultiSource(sources))
        t = r.telemetry
        return SSSPResult(r.dist, r.pred, t.buckets, t.inner_iters,
                          t.overflow)


def delta_stepping(graph: COOGraph, source: int,
                   config: DeltaConfig = DeltaConfig()) -> SSSPResult:
    """**Deprecated** one-shot convenience wrapper (prefer
    ``repro.api.Engine(graph, config).plan().solve(SingleSource(s))``).
    ``config="auto"`` picks Δ from graph statistics (DESIGN.md §7)."""
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        solver = DeltaSteppingSolver(graph, config)
    warnings.warn(
        "delta_stepping is deprecated: use repro.api.Engine(graph, config)"
        ".plan().solve(SingleSource(source)) (DESIGN.md §10)",
        DeprecationWarning, stacklevel=2)
    return solver.solve(source)
