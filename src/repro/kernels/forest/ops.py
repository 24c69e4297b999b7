"""Public wrapper for the forest-inference kernel.

``pack_tables`` re-lays a dense forest (``core/forest_jax.DenseForest``,
tree-major, level ``d`` at nodes [2^d-1, 2^{d+1}-1)) into the kernel's
node-major tables with every level starting on an 8-row boundary, trees
padded to a multiple of 128 lanes. Padded trees always go left and hold
leaf value 0, so they add nothing to the sum; the kernel divides by the
real tree count.

``PallasForest`` packs once, keeps the tables on one device, and answers
``(B, F) -> (B,)``. Whether the kernel runs compiled or interpreted follows
that device's platform (``core.platform.pallas_interpret``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ...core.platform import pallas_interpret
from .kernel import (LANES, forest_predict_kernel, leaf_rows,
                     level_offsets)


def pack_tables(feature, threshold, value, depth: int):
    """(T, N) dense arrays -> (feature, threshold, leaf) kernel tables."""
    feature = np.asarray(feature, dtype=np.int32)
    threshold = np.asarray(threshold, dtype=np.float32)
    value = np.asarray(value, dtype=np.float32)
    T = feature.shape[0]
    Tp = -(-T // LANES) * LANES
    offs = level_offsets(depth)
    feat = np.full((offs[-1], Tp), -1, dtype=np.int32)
    thr = np.full((offs[-1], Tp), np.inf, dtype=np.float32)
    for d in range(depth):
        lo, w = 2 ** d - 1, 2 ** d
        feat[offs[d]:offs[d] + w, :T] = feature[:, lo:lo + w].T
        thr[offs[d]:offs[d] + w, :T] = threshold[:, lo:lo + w].T
    w = 2 ** depth
    leaf = np.zeros((leaf_rows(depth), Tp), dtype=np.float32)
    leaf[:w, :T] = value[:, w - 1:2 * w - 1].T
    return feat, thr, leaf


class PallasForest:
    """A dense forest's kernel tables on one device, callable on (B, F)."""

    def __init__(self, feature, threshold, value, depth: int, *,
                 device=None, block_b: int = 64):
        device = device if device is not None else jax.devices()[0]
        self.tables = tuple(jax.device_put(t, device) for t in
                            pack_tables(feature, threshold, value, depth))
        self.static = dict(depth=int(depth), n_trees=int(len(feature)),
                           block_b=block_b,
                           interpret=pallas_interpret(device))

    @classmethod
    def from_dense(cls, dense, **kw) -> "PallasForest":
        return cls(dense.feature, dense.threshold, dense.value, dense.depth,
                   **kw)

    def __call__(self, x) -> jax.Array:
        return forest_predict_kernel(jnp.asarray(x, dtype=jnp.float32),
                                     *self.tables, **self.static)

    def lower(self, x):
        """The jax ``Lowered`` of exactly what ``__call__(x)`` runs."""
        return forest_predict_kernel.lower(
            jnp.asarray(x, dtype=jnp.float32), *self.tables, **self.static)


def forest_predict(x, feature, threshold, value, *, depth: int,
                   block_b: int = 64):
    """Predict with DenseForest arrays in one call. Returns (B,) float32.

    x: (B, F). feature/threshold/value: (T, N) with N = 2^(depth+1)-1."""
    return PallasForest(feature, threshold, value, depth,
                        block_b=block_b)(x)
