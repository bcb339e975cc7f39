"""R-MAT (Chakrabarti et al.): the recursive quadrant draw with the
configuration's probabilities ``a, b, c, d``, ``n_nodes`` vertices and
exactly ``n_edges`` edges, weights uniform in ``[w_lo, w_hi]``.

Vertex ids are drawn at scale ``ceil(log2 n)`` and folded modulo ``n``.
Self-loops are kept (they never relax an edge with weight >= 1), so the
edge count is fixed and every seed compiles one program. Requests start
at vertices with out-degree >= 1 (Graph500's search-key rule) and may
end anywhere; the warm-up starts at a vertex without out-edges."""
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.graphs import Deployment


@partial(jax.jit,
         static_argnames=("n", "m", "a", "b", "c", "w_lo", "w_hi"))
def _rmat(key, *, n, m, a, b, c, w_lo, w_hi):
    scale = max(1, math.ceil(math.log2(max(n, 2))))
    kq, kw = jax.random.split(key)

    def level(lvl, sd):
        src, dst = sd
        r = jax.random.uniform(jax.random.fold_in(kq, lvl), (m,))
        right = ((r >= a) & (r < a + b)) | (r >= a + b + c)
        down = r >= a + b
        return (src * 2 + down.astype(jnp.int32),
                dst * 2 + right.astype(jnp.int32))

    zero = jnp.zeros((m,), jnp.int32)
    src, dst = jax.lax.fori_loop(0, scale, level, (zero, zero))
    src, dst = src % n, dst % n
    w = jax.random.randint(kw, (m,), w_lo, w_hi + 1, jnp.int32)
    outdeg = jnp.zeros((n,), jnp.int32).at[src].add(1)
    return src, dst, w, outdeg


def build(cfg: dict, key) -> Deployment:
    n, m = int(cfg["n_nodes"]), int(cfg["n_edges"])
    src, dst, w, outdeg = _rmat(
        key, n=n, m=m, a=cfg["a"], b=cfg["b"], c=cfg["c"],
        w_lo=int(cfg["w_lo"]), w_hi=int(cfg["w_hi"]))
    has_out = np.asarray(jax.device_get(outdeg)) > 0
    return Deployment(src, dst, w, n, None, np.flatnonzero(has_out),
                      np.arange(n), int(np.flatnonzero(~has_out)[0]), m)
