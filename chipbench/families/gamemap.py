"""Game map: a ``rows`` x ``cols`` occupancy grid, each cell an obstacle
with probability ``obstacle_frac``, 8-neighbour moves between free
cells at ``cost_straight`` and ``cost_diag``.

The free-to-free edge list is compacted into a fixed capacity well
above its expected size and padded with sentinel edges
(``src == dst == n``), which the engine's edge consumers treat as
inactive, so every seed compiles one program. Cell (0, 0) is walled in
by its three neighbours: a free vertex with no edges, so a warm-up
solve from it runs one bucket. Origins and destinations are drawn from
the largest 8-connected free region, as pathfinding scenario sets pick
reachable pairs.

The engine's stencil costs (``engine.grid_costs``) have to be the
map's."""
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.graphs import Deployment


def grid_moves(straight: int, diag: int):
    return ((-1, 0, straight), (1, 0, straight), (0, -1, straight),
            (0, 1, straight), (-1, -1, diag), (-1, 1, diag),
            (1, -1, diag), (1, 1, diag))


def lattice_edges(h: int, w: int) -> int:
    """Directed 8-neighbour pairs of an h x w grid."""
    return 2 * (h * (w - 1) + w * (h - 1)) + 4 * (h - 1) * (w - 1)


def edge_capacity(h: int, w: int, obstacle_frac: float) -> int:
    """Fixed edge capacity of a map: the expected free-to-free count
    plus 32 square roots of the lattice size (the count's standard
    deviation is about one square root), in whole 1024-edge blocks."""
    lat = lattice_edges(h, w)
    want = lat * (1.0 - obstacle_frac) ** 2 + 32.0 * math.sqrt(lat)
    return min(lat, -(-int(want) // 1024) * 1024)


@partial(jax.jit,
         static_argnames=("h", "w", "obstacle_frac", "straight", "diag",
                          "cap"))
def _gamemap(key, *, h, w, obstacle_frac, straight, diag, cap):
    free = jax.random.uniform(key, (h, w)) >= obstacle_frac
    free = free.at[0, 0].set(True).at[0, 1].set(False)
    free = free.at[1, 0].set(False).at[1, 1].set(False)
    idx = jnp.arange(h * w, dtype=jnp.int32).reshape(h, w)
    srcs, dsts, ws, oks = [], [], [], []
    for dr, dc, cost in grid_moves(straight, diag):
        rs = slice(max(0, -dr), h - max(0, dr))
        cs = slice(max(0, -dc), w - max(0, dc))
        rd = slice(max(0, dr), h + min(0, dr))
        cd = slice(max(0, dc), w + min(0, dc))
        srcs.append(idx[rs, cs].ravel())
        dsts.append(idx[rd, cd].ravel())
        oks.append((free[rs, cs] & free[rd, cd]).ravel())
        ws.append(jnp.full(srcs[-1].shape, cost, jnp.int32))
    ok = jnp.concatenate(oks)
    count = ok.sum()
    sel = jnp.nonzero(ok, size=cap, fill_value=ok.shape[0])[0]
    sent = jnp.full((1,), h * w, jnp.int32)
    src = jnp.concatenate(srcs + [sent])[sel]
    dst = jnp.concatenate(dsts + [sent])[sel]
    wt = jnp.concatenate(ws + [jnp.zeros((1,), jnp.int32)])[sel]
    return src, dst, wt, free, count


def largest_component(free: np.ndarray) -> np.ndarray:
    """Flat ids of the largest 8-connected region of free cells."""
    import scipy.ndimage

    labels, k = scipy.ndimage.label(free, structure=np.ones((3, 3), int))
    if k == 0:
        raise ValueError("the map has no free cell")
    sizes = np.bincount(labels.ravel())
    sizes[0] = 0
    return np.flatnonzero(labels.ravel() == int(sizes.argmax()))


def build(cfg: dict, key) -> Deployment:
    h, wd = int(cfg["rows"]), int(cfg["cols"])
    straight, diag = int(cfg["cost_straight"]), int(cfg["cost_diag"])
    stencil = cfg.get("engine", {}).get("grid_costs")
    if stencil is not None and tuple(stencil) != (straight, diag):
        raise ValueError(f"engine.grid_costs {stencil} are not the map's "
                         f"costs ({straight}, {diag})")
    frac = float(cfg["obstacle_frac"])
    cap = edge_capacity(h, wd, frac)
    src, dst, w, free, count = _gamemap(
        key, h=h, w=wd, obstacle_frac=frac, straight=straight, diag=diag,
        cap=cap)
    count = int(count)
    if count > cap:
        raise RuntimeError(f"{count} free-to-free edges exceed the fixed "
                           f"capacity {cap}")
    region = largest_component(np.asarray(jax.device_get(free)))
    return Deployment(src, dst, w, h * wd, free, region, region, 0, count,
                      grid=(h, wd))
