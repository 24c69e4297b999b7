"""JAX inference paths for the fitted forest.

Two layouts:

1. ``FlatForest`` (exact): sparse node arrays + gather-based traversal.
   Works for unbounded-depth trees; jit-compiled; the exact device path on
   any platform, the TPU included.

2. ``DenseForest``: every tree is embedded into a *complete* binary tree
   of fixed depth D (child index = 2i+1 / 2i+2, no child pointers).
   Traversal is level-synchronous; on TPU the Pallas kernel
   (``kernels/forest``) reads each level's nodes as vector selects, with no
   dynamic gathers. Trees deeper than D are truncated: the cut subtree is
   replaced by its node value (the node's training-set mean), which is why
   auto backend selection leaves this layout out for deeper forests.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .forest import ExtraTreesRegressor, FlatForest


# ---------------------------------------------------------------- flat (exact)

@partial(jax.jit, static_argnames=("max_depth",))
def _predict_flat_jax(feature, threshold, left, right, value, roots, x,
                      max_depth: int):
    B = x.shape[0]
    T = roots.shape[0]
    cur = jnp.broadcast_to(roots[None, :], (B, T)).astype(jnp.int32)

    def body(_, cur):
        feat = jnp.take(feature, cur)                 # (B, T)
        active = feat >= 0
        f = jnp.where(active, feat, 0)
        xv = jnp.take_along_axis(x, f, axis=1)        # (B, T) gather from (B, F)
        thr = jnp.take(threshold, cur)
        nxt = jnp.where(xv <= thr, jnp.take(left, cur), jnp.take(right, cur))
        return jnp.where(active, nxt, cur)

    cur = jax.lax.fori_loop(0, max_depth, body, cur)
    return jnp.take(value, cur).mean(axis=1)


class FlatForestJax:
    """jit-wrapped exact inference over a FlatForest."""

    def __init__(self, forest: FlatForest):
        self.arrays = tuple(jnp.asarray(a) for a in (
            forest.feature, forest.threshold, forest.left, forest.right,
            forest.value, forest.roots))
        self.max_depth = int(forest.max_depth)

    def __call__(self, x: np.ndarray | jax.Array) -> jax.Array:
        x = jnp.asarray(x, dtype=jnp.float32)
        return _predict_flat_jax(*self.arrays, x, max_depth=self.max_depth)


# ------------------------------------------------------------- dense (TPU path)

@dataclass
class DenseForest:
    """Complete-binary-tree layout, one row per tree.

    node i children are 2i+1, 2i+2; level ``d`` occupies [2^d - 1, 2^{d+1}-1).
    ``feature`` is -1 at virtual/leaf nodes; their ``threshold`` is +inf so
    traversal always takes the left child whose value repeats the parent's
    (self-replicating leaves), keeping the level loop branch-free.
    """
    feature: np.ndarray    # (T, N) int32
    threshold: np.ndarray  # (T, N) float32
    value: np.ndarray      # (T, N) float32
    depth: int
    n_features: int

    @property
    def n_trees(self) -> int:
        return int(self.feature.shape[0])

    @property
    def n_nodes(self) -> int:
        return int(self.feature.shape[1])


def to_dense(est: ExtraTreesRegressor, depth: int,
             n_trees: int | None = None) -> DenseForest:
    trees = est.trees_ if n_trees is None else est.trees_[:n_trees]
    T = len(trees)
    N = 2 ** (depth + 1) - 1
    feature = np.full((T, N), -1, dtype=np.int32)
    threshold = np.full((T, N), np.float32(np.inf))
    value = np.zeros((T, N), dtype=np.float32)
    for ti, t in enumerate(trees):
        # embed: (sparse node, dense slot, level). Traversal always walks
        # exactly ``depth`` levels, so only values at level ``depth`` are ever
        # read; terminal nodes (+inf threshold => always-left) replicate their
        # value down the left spine to that level.
        stack = [(0, 0, 0)]
        while stack:
            s, d, lvl = stack.pop()
            if t.feature[s] >= 0 and lvl < depth:
                feature[ti, d] = t.feature[s]
                threshold[ti, d] = t.threshold[s]
                stack.append((int(t.left[s]), 2 * d + 1, lvl + 1))
                stack.append((int(t.right[s]), 2 * d + 2, lvl + 1))
            else:
                val = t.value[s]        # leaf value, or truncated-subtree mean
                dd, l = d, lvl
                value[ti, dd] = val
                while l < depth:
                    dd = 2 * dd + 1
                    l += 1
                    value[ti, dd] = val
    return DenseForest(feature=feature, threshold=threshold, value=value,
                       depth=depth, n_features=est.n_features_)


def dense_leaf_sum(feature, threshold, value, x, depth: int,
                   axis_name: str | None = None):
    """SUM of per-tree leaf values, (B,) — the shard-combinable core of dense
    traversal. Inert (padded) trees carry value 0 everywhere and contribute
    nothing, so a partitioned forest's prediction is
    ``sum(shard sums) / n_real_trees`` — a psum across shards when the tree
    axis is device-partitioned (``serve/sharded.py``). Traceable: call from
    inside jit / shard_map; inside ``shard_map`` pass the tree mesh axis as
    ``axis_name``, over which the traversal state varies."""
    B = x.shape[0]
    T = feature.shape[0]
    cur = jnp.zeros((B, T), dtype=jnp.int32)
    if axis_name is not None:
        cur = jax.lax.pcast(cur, axis_name, to="varying")
    trees = jnp.arange(T)[None, :]

    def body(_, cur):
        feat = feature[trees, cur]                    # (B, T)
        f = jnp.maximum(feat, 0)
        xv = jnp.take_along_axis(x, f, axis=1)
        thr = threshold[trees, cur]
        go_left = jnp.where(feat >= 0, xv <= thr, True)
        return jnp.where(go_left, 2 * cur + 1, 2 * cur + 2)

    cur = jax.lax.fori_loop(0, depth, body, cur)
    return value[trees, cur].sum(axis=1)


@partial(jax.jit, static_argnames=("depth",))
def _predict_dense_jax(feature, threshold, value, x, depth: int):
    """Reference dense traversal with gathers (oracle for the Pallas kernel)."""
    return dense_leaf_sum(feature, threshold, value, x, depth) / feature.shape[0]


class DenseForestJax:
    def __init__(self, forest: DenseForest):
        self.feature = jnp.asarray(forest.feature)
        self.threshold = jnp.asarray(forest.threshold)
        self.value = jnp.asarray(forest.value)
        self.depth = int(forest.depth)

    def __call__(self, x) -> jax.Array:
        x = jnp.asarray(x, dtype=jnp.float32)
        return _predict_dense_jax(self.feature, self.threshold, self.value, x,
                                  depth=self.depth)
