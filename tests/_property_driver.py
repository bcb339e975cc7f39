"""Shared property-test driver: hypothesis-driven when hypothesis is
installed (the CI tier-1 install includes it via requirements-dev.txt,
so property suites never skip there), deterministic seed sweep
otherwise — the suites degrade to fewer examples, never to zero.

Used by tests/test_differential.py and tests/test_property_sssp.py; the
two files share this one implementation so a fix to the fallback
seeding or the ``@given`` wrapping cannot silently diverge between
them.
"""
import jax
import numpy as np

# Every RelaxBackend strategy the engine exposes, in registration order —
# the single source of truth for "the full differential matrix". The
# differential suite consumes this tuple so a newly added backend cannot
# silently stay out of the oracle cross product.
ALL_STRATEGIES = ("edge", "ell", "pallas", "fused",
                  "sharded_edge", "sharded_ell", "sharded_fused")

# Every frontier-selection policy (DESIGN.md §15), same contract: the
# differential suites consume this tuple, so a newly added policy joins
# the oracle cross product automatically. Pinned equal to
# repro.core.policies.POLICIES by tests/test_policies.py.
ALL_POLICIES = ("delta", "rho", "radius")

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    st = None
    HAVE_HYPOTHESIS = False


def drive(max_examples: int, fallback_examples: int, strategy,
          fallback_draw):
    """Property-driver decorator.

    ``strategy``      — callable ``st -> hypothesis strategy`` (built
                        lazily so importing this module never needs
                        hypothesis);
    ``fallback_draw`` — callable ``rng -> one drawn value`` emulating
                        the strategy with a seeded numpy Generator.
    """
    if HAVE_HYPOTHESIS:
        def deco(fn):
            return settings(max_examples=max_examples, deadline=None)(
                given(strategy(st))(fn))
        return deco

    def deco(fn):
        def run_sweep():
            rng = np.random.default_rng(0)
            for _ in range(fallback_examples):
                fn(fallback_draw(rng))
        run_sweep.__name__ = fn.__name__
        run_sweep.__doc__ = fn.__doc__
        return run_sweep
    return deco


class null_ctx:
    """No-op context manager (stand-in for enable_x64 in non-packed
    parametrizations)."""

    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


# XLA:CPU maps memory for every executable it compiles. The cross-product
# suites compile thousands of program variants (each adversarial seed
# brings its own ELL widths), and a process past the kernel's
# memory-map budget (vm.max_map_count, 65530 by default) segfaults
# inside its next compile.
MAX_MAPPINGS = 55_000


def release_compiled_programs(limit: int = MAX_MAPPINGS) -> None:
    """Drop JAX's caches — and with them the compiled executables — once
    this process holds more than ``limit`` memory mappings. Programs
    recompile on their next use; answers are unaffected. A no-op where
    ``/proc/self/maps`` does not exist."""
    try:
        with open("/proc/self/maps") as f:
            n = f.read().count("\n")
    except OSError:
        return
    if n > limit:
        jax.clear_caches()
