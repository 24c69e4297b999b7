"""JAX inference paths for the fitted forest.

Two layouts:

1. ``FlatForest`` (exact): trees of any depth, walked by
   ``_predict_flat_jax``, the one jitted program of the ``flat-jax``
   backend. It has two bodies; ``FlatForestJax`` picks one by the platform
   of the device its tables live on (``core/platform.flat_walk``) and keeps
   only that body's arrays:

   * ``gathers`` (every platform but the TPU): per level, per-element
     gathers of the node's feature, threshold, children and of ``x`` from
     the sparse node arrays. Gathers are cheap on a CPU.
   * ``levels`` (TPU): ``pack_levels`` lays level ``l`` of every tree out
     in slots of ``(L, W, T)`` tables; the walk reads a level's node at a
     lane's slot by compare-and-select over the ``W`` slots, and ``x`` at
     the node's feature by a select over the features. Dense,
     lane-parallel work in place of gathers, which a TPU serializes per
     element.

   Both bodies compare ``x <= threshold`` in float32 and average the same
   leaf matrix, so their answers are bit-identical.

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
from .platform import flat_walk


# ---------------------------------------------------------------- flat (exact)

def pack_levels(flat: FlatForest):
    """Level-packed node tables ``(feature, threshold, child, value)``, each
    ``(L, W, T)``: ``L = max_depth + 1`` levels, ``W`` the widest level of
    any tree rounded up to 8, ``T`` trees.

    Level ``l`` of tree ``t`` fills slots ``[0, n)`` of ``[l, :, t]``. The
    ``k``-th internal node of a level, in slot order, has its children in
    slots ``2k`` (left) and ``2k + 1`` (right) of the next level, and
    ``child`` holds ``2k``. ``feature`` is -1 at leaves and padding;
    ``value`` holds a leaf's value and 0 elsewhere; ``threshold`` and
    ``child`` are 0 where unused. Built from the forest's global child
    indices, one level at a time, whatever the order of the nodes.
    """
    n_trees = flat.n_trees
    n_levels = flat.max_depth + 1
    node = flat.roots.astype(np.int64)
    tree = np.arange(n_trees)            # sorted by (tree, slot) throughout
    slot = np.zeros(n_trees, dtype=np.int64)
    levels = []
    while node.size:
        if len(levels) == n_levels:
            raise ValueError(f"a tree is deeper than max_depth "
                             f"{flat.max_depth}")
        inner = flat.feature[node] >= 0
        parents = tree[inner]
        rank = np.arange(parents.size) - np.searchsorted(parents, parents)
        levels.append((node, tree, slot, inner, 2 * rank))
        node = np.stack([flat.left[node[inner]], flat.right[node[inner]]],
                        axis=1).ravel()
        tree = np.repeat(parents, 2)
        slot = (2 * rank[:, None] + np.arange(2)).ravel()
    width = max(int(lv[2].max()) + 1 for lv in levels)
    width = -(-width // 8) * 8
    shape = (n_levels, width, n_trees)
    feature = np.full(shape, -1, dtype=np.int32)
    threshold = np.zeros(shape, dtype=np.float32)
    child = np.zeros(shape, dtype=np.int32)
    value = np.zeros(shape, dtype=np.float32)
    for lvl, (node, tree, slot, inner, kids) in enumerate(levels):
        at = (lvl, slot[inner], tree[inner])
        feature[at] = flat.feature[node[inner]]
        threshold[at] = flat.threshold[node[inner]]
        child[at] = kids
        leaf = ~inner
        value[lvl, slot[leaf], tree[leaf]] = flat.value[node[leaf]]
    return feature, threshold, child, value


def _gather_leaves(feature, threshold, left, right, value, roots, x,
                   max_depth: int):
    """(B, T) leaf value of each row in each tree, by per-element gathers
    from the flat node arrays."""
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
    return jnp.take(value, cur)


def _select(at, table):
    """``table``'s entry where ``at`` holds, reduced over axis 0. At most one
    entry along that axis holds, so the int32 sum of the bit patterns, the
    rest zeros, is that entry to the bit (0 where none holds)."""
    bits = jax.lax.bitcast_convert_type(table, jnp.int32)
    got = jnp.where(at, bits, 0).sum(axis=0, dtype=jnp.int32)
    return jax.lax.bitcast_convert_type(got, table.dtype)


def _level_leaves(feature, threshold, child, value, x, max_depth: int):
    """(B, T) leaf value of each row in each tree, by compare-and-select
    over the slots of ``pack_levels``' tables, one level at a time."""
    B, F = x.shape
    _, W, T = feature.shape
    slots = jnp.arange(W, dtype=jnp.int32)[:, None, None]
    feats = jnp.arange(F, dtype=jnp.int32)[:, None, None]
    xs = x.T[:, :, None]                              # (F, B, 1)

    def body(lvl, state):
        cur, acc = state                  # slot, or -1 past the leaf; (B, T)
        at = cur[None] == slots                       # (W, B, T)
        feat, thr, kid, val = (_select(at, t[lvl][:, None, :])
                               for t in (feature, threshold, child, value))
        xv = _select(feat[None] == feats, xs)         # x[b, feat[b, t]]
        live = cur >= 0
        acc = jnp.where(live & (feat < 0), val, acc)
        cur = jnp.where(live & (feat >= 0),
                        kid + jnp.where(xv <= thr, 0, 1), -1)
        return cur, acc

    state = (jnp.zeros((B, T), jnp.int32), jnp.zeros((B, T), jnp.float32))
    _, acc = jax.lax.fori_loop(0, max_depth + 1, body, state)
    return acc


_LEAVES = {"gathers": _gather_leaves, "levels": _level_leaves}


@partial(jax.jit, static_argnames=("max_depth", "walk"))
def _predict_flat_jax(*args, max_depth: int, walk: str = "gathers"):
    """The ``flat-jax`` program: ``args`` are the walk's node arrays, then
    ``x`` (B, F) float32; returns (B,) float32. ``walk="gathers"`` takes
    ``FlatForest``'s ``(feature, threshold, left, right, value, roots)``,
    ``walk="levels"`` the four tables of ``pack_levels``."""
    *nodes, x = args
    return _LEAVES[walk](*nodes, x, max_depth).mean(axis=1)


class FlatForestJax:
    """jit-wrapped exact inference over a FlatForest on the first device.
    ``walk`` follows that device's platform (``core/platform.flat_walk``);
    ``arrays`` holds that walk's node arrays and no others."""

    def __init__(self, forest: FlatForest):
        device = jax.devices()[0]
        self.walk = flat_walk(device)
        if self.walk == "levels":
            arrays = pack_levels(forest)
        else:
            arrays = (forest.feature, forest.threshold, forest.left,
                      forest.right, forest.value, forest.roots)
        self.arrays = tuple(jax.device_put(a, device) for a in arrays)
        self.max_depth = int(forest.max_depth)

    def __call__(self, x: np.ndarray | jax.Array) -> jax.Array:
        x = jnp.asarray(x, dtype=jnp.float32)
        return _predict_flat_jax(*self.arrays, x, max_depth=self.max_depth,
                                 walk=self.walk)


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
