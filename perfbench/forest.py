"""The benchmark's own forest: its fit from the seed, its plain walk, and
the walk's lower-precision control. Numpy only; imports nothing of the
program.

``fit`` grows extremely randomized trees as scikit-learn's
``ExtraTreesRegressor`` does with its defaults (Geurts et al. 2006; the
paper's §3.3): every non-constant feature is a candidate at every node, one
threshold is drawn uniformly in [min, max) of the feature at the node, the
candidate with the least summed squared error wins, no bootstrap, nodes
split until pure, single-sample or at ``max_depth``. The trees grow level
by level, all of them at once, so a 512-tree forest of depth ~35 on 328
rows takes about a second. Nodes are numbered breadth first within each
tree (a parent precedes its children); ``left``/``right`` are tree-local.

``walk`` is the plain reference: each tree walked from its root by
``x[feature] <= threshold`` in float32, the float32 leaf values averaged in
float64. ``walk_bf16`` is the same walk with features, thresholds and leaf
values rounded to bfloat16 and the mean taken in float32: the control that
``correct`` has to reject.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

FIELDS = ("feature", "threshold", "left", "right", "value", "n_samples",
          "impurity")


@dataclass
class Forest:
    """Trees as concatenated node arrays; tree ``t`` owns nodes
    ``offsets[t]:offsets[t+1]``, with tree-local child indices."""
    feature: np.ndarray     # int32, -1 at leaves
    threshold: np.ndarray   # float32
    left: np.ndarray        # int32, tree-local, -1 at leaves
    right: np.ndarray       # int32
    value: np.ndarray       # float32: mean target of the node's samples
    n_samples: np.ndarray   # int32
    impurity: np.ndarray    # float32: variance of the node's targets
    offsets: np.ndarray     # int64, (n_trees + 1,)
    n_features: int

    @property
    def n_trees(self) -> int:
        return len(self.offsets) - 1

    def tree(self, t: int) -> dict[str, np.ndarray]:
        lo, hi = self.offsets[t], self.offsets[t + 1]
        return {k: getattr(self, k)[lo:hi] for k in FIELDS}

    def save(self, path) -> None:
        np.savez(path, **{k: getattr(self, k) for k in FIELDS},
                 offsets=self.offsets, n_features=self.n_features)

    @classmethod
    def load(cls, path) -> "Forest":
        with np.load(path) as z:
            return cls(**{k: z[k] for k in (*FIELDS, "offsets")},
                       n_features=int(z["n_features"]))


def fit(X: np.ndarray, y: np.ndarray, n_trees: int, max_depth: int | None,
        rng: np.random.Generator) -> Forest:
    X = np.ascontiguousarray(X, np.float32)
    y = np.asarray(y, np.float64)
    n, F = X.shape
    cap = 2 * n - 1                         # most nodes a tree can have
    shape = (n_trees, cap)
    feature = np.full(shape, -1, np.int32)
    threshold = np.zeros(shape, np.float32)
    left = np.full(shape, -1, np.int32)
    right = np.full(shape, -1, np.int32)
    value = np.zeros(shape, np.float32)
    n_samples = np.zeros(shape, np.int32)
    impurity = np.zeros(shape, np.float32)
    n_nodes = np.ones(n_trees, np.int64)

    # one entry per (tree, sample) still in a node that may split
    pt = np.repeat(np.arange(n_trees), n)
    pi = np.tile(np.arange(n), n_trees)
    pn = np.zeros(n_trees * n, np.int64)
    depth = 0
    while len(pt):
        order = np.argsort(pt * cap + pn, kind="stable")
        pt, pi, pn = pt[order], pi[order], pn[order]
        key = pt * cap + pn
        starts = np.flatnonzero(np.r_[True, key[1:] != key[:-1]])
        gt, gn = pt[starts], pn[starts]
        cnt = np.diff(np.r_[starts, len(key)])
        ys = y[pi]
        s_all = np.add.reduceat(ys, starts)
        q_all = np.add.reduceat(ys * ys, starts)
        mean = s_all / cnt
        var = np.maximum(q_all / cnt - mean ** 2, 0.0)
        value[gt, gn] = mean
        n_samples[gt, gn] = cnt
        impurity[gt, gn] = var
        if max_depth is not None and depth >= max_depth:
            break

        Xs = X[pi]
        grp = np.repeat(np.arange(len(starts)), cnt)
        lo = np.minimum.reduceat(Xs, starts)
        hi = np.maximum.reduceat(Xs, starts)
        thr = (lo + rng.uniform(size=lo.shape) * (hi.astype(np.float64) - lo)
               ).astype(np.float32)
        mask = (Xs <= thr[grp]).astype(np.float64)
        n_l = np.add.reduceat(mask, starts)
        s_l = np.add.reduceat(mask * ys[:, None], starts)
        q_l = np.add.reduceat(mask * (ys * ys)[:, None], starts)
        n_r = cnt[:, None] - n_l
        s_r = s_all[:, None] - s_l
        q_r = q_all[:, None] - q_l
        ok = (hi > lo) & (n_l >= 1) & (n_r >= 1)
        with np.errstate(divide="ignore", invalid="ignore"):
            sse = (q_l - s_l ** 2 / n_l) + (q_r - s_r ** 2 / n_r)
        score = np.where(ok, sse, np.inf)
        best = np.argmin(score, axis=1)
        split = (np.isfinite(score[np.arange(len(best)), best])
                 & (cnt >= 2) & (var > 1e-12))

        sg = np.flatnonzero(split)          # sorted by (tree, node)
        st = gt[sg]
        first = np.searchsorted(st, st)     # first split group of each tree
        child = n_nodes[st] + 2 * (np.arange(len(sg)) - first)
        feature[st, gn[sg]] = best[sg]
        threshold[st, gn[sg]] = thr[sg, best[sg]]
        left[st, gn[sg]] = child
        right[st, gn[sg]] = child + 1
        np.add.at(n_nodes, st, 2)

        child_of = np.full(len(starts), -1, np.int64)
        child_of[sg] = child
        keep = split[grp]
        g = grp[keep]
        go_left = Xs[keep, best[g]] <= thr[g, best[g]]
        pt, pi = pt[keep], pi[keep]
        pn = child_of[g] + np.where(go_left, 0, 1)
        depth += 1

    offsets = np.r_[0, np.cumsum(n_nodes)]
    pick = np.arange(cap)[None, :] < n_nodes[:, None]
    return Forest(feature=feature[pick], threshold=threshold[pick],
                  left=left[pick], right=right[pick], value=value[pick],
                  n_samples=n_samples[pick], impurity=impurity[pick],
                  offsets=offsets.astype(np.int64), n_features=F)


def _leaves(forest: Forest, t: int, X: np.ndarray, thr: np.ndarray) -> np.ndarray:
    lo = forest.offsets[t]
    feat = forest.feature[lo:forest.offsets[t + 1]]
    th = thr[lo:forest.offsets[t + 1]]
    lft = forest.left[lo:forest.offsets[t + 1]]
    rgt = forest.right[lo:forest.offsets[t + 1]]
    rows = np.arange(len(X))
    cur = np.zeros(len(X), np.int64)
    live = rows
    while len(live):
        node = cur[live]
        f = feat[node]
        inner = f >= 0
        live, node, f = live[inner], node[inner], f[inner]
        go_left = X[live, f] <= th[node]
        cur[live] = np.where(go_left, lft[node], rgt[node])
    return lo + cur


def walk(forest: Forest, X: np.ndarray) -> np.ndarray:
    """(N, F) -> (N,) float64: the mean leaf value over all trees."""
    X = np.ascontiguousarray(X, np.float32)
    acc = np.zeros(len(X), np.float64)
    for t in range(forest.n_trees):
        acc += forest.value[_leaves(forest, t, X, forest.threshold)]
    return acc / forest.n_trees


def walk_bf16(forest: Forest, X: np.ndarray) -> np.ndarray:
    """The control: ``walk`` with inputs, thresholds and leaf values in
    bfloat16 and the mean in float32."""
    from ml_dtypes import bfloat16

    def bf(a):
        return np.asarray(a, np.float32).astype(bfloat16).astype(np.float32)

    Xb, thr, val = bf(X), bf(forest.threshold), bf(forest.value)
    acc = np.zeros(len(X), np.float32)
    for t in range(forest.n_trees):
        acc += val[_leaves(forest, t, Xb, thr)]
    return (acc / np.float32(forest.n_trees)).astype(np.float64)
