"""Graph deployments made on the device from the configuration's
``graph_seed``, in the int32 types the engine serves.

A configuration names its graph ``family``; ``families/<family>.py``
makes it with ``build(cfg, key) -> Deployment``, in one jitted call and
with the same shapes for every graph seed, so that one compiled program
serves them all. A new family is a new file there.

Nothing here imports the program; ``run.py`` wraps the arrays into the
program's graph type.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.plugins import load


def seed_key(seed: int, purpose: int):
    """A JAX key from a seed of any size (more than 32 bits included)."""
    words = np.random.SeedSequence([int(seed), purpose]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


@dataclasses.dataclass
class Deployment:
    """What a run serves: device edge arrays, the vertex count, the
    game map's free mask and grid shape (``None`` for other graphs) and
    the host-side vertex sets traffic draws from."""

    src: object
    dst: object
    w: object
    n: int
    free: object                 # device bool[H, W] or None
    sources: np.ndarray          # vertices requests may start from
    targets: np.ndarray          # vertices requests may end at
    warm_source: int             # a vertex with no out-edges
    n_real_edges: int
    grid: Optional[tuple] = None  # (rows, cols) of a grid graph


def build(cfg: dict) -> Deployment:
    """Make the configuration's graph from its ``graph_seed`` on the
    default device. One graph for every run of a cell: runs differ in
    which answers they check, not in how much work the graph holds."""
    family = load("families", cfg["family"])
    return family.build(cfg, seed_key(int(cfg["graph_seed"]), 1))
