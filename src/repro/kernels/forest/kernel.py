"""Random-forest inference as a Pallas TPU kernel.

The paper's deployment bottleneck is prediction latency: 15-108 ms per
prediction for 256-1024 trees of average depth ~33 on a Xeon (paper Tables
4/5), too slow for sub-millisecond scheduling (paper §7.1). CPU forest
inference is pointer-chasing; this kernel turns it into branch-free vector
work:

  * trees are *complete binary trees* of static depth D (the dense layout of
    ``core/forest_jax.DenseForest``); traversal is level-synchronous, every
    (sample, tree) pair advancing one level per step;
  * trees lie on the 128 lanes and samples on the sublanes, so the state is
    one int32 ``cur[b, t]`` (the level-local node index) per lane;
  * "which feature and threshold does my node test" is a select over the
    level's nodes: the node tables are read 8 rows (one vreg) at a time and
    each row is kept where ``cur`` equals its index. Reading x at that
    feature is the same select over the F features. No gathers, no float
    rounding: the comparison ``x <= threshold`` is the tree-walk's own.

Node tables are node-major, ``(rows, trees)``, each level starting on a
multiple of 8 rows (``level_offsets``) so that every load is tile-aligned.
Grid: (tree tiles, batch tiles), batch innermost so a tree tile's tables
are fetched once; the output holds one leaf value per (sample, tree) and
the mean over trees is taken outside the kernel. Per step, VMEM holds the
x tile, two internal-node tables and the leaf table: at D=10 and 128 trees
that is 1040 + 1040 + 1024 rows of 512 bytes, ~1.6 MB, twice for double
buffering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

SUBLANES = 8      # node-table rows per load
LANES = 128       # trees per tile


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def level_offsets(depth: int) -> list[int]:
    """Row where each level's nodes start in the internal-node tables; the
    last entry is the tables' row count."""
    offs = [0]
    for d in range(depth):
        offs.append(offs[-1] + _round_up(2 ** d, SUBLANES))
    return offs


def leaf_rows(depth: int) -> int:
    return _round_up(2 ** depth, SUBLANES)


def _select(cur, width: int, base: int, tables, fills):
    """Per lane, the entry of each table at level-local node ``cur`` of a
    level of ``width`` nodes starting at row ``base``."""
    def chunk(c, acc):
        start = pl.multiple_of(base + c * SUBLANES, SUBLANES)
        rows = [t[pl.ds(start, SUBLANES), :] for t in tables]
        for k in range(SUBLANES):
            hit = cur == c * SUBLANES + k
            acc = tuple(jnp.where(hit, r[k:k + 1, :], a)
                        for r, a in zip(rows, acc))
        return acc

    init = tuple(jnp.full(cur.shape, f, t.dtype)
                 for t, f in zip(tables, fills))
    return jax.lax.fori_loop(0, -(-width // SUBLANES), chunk, init)


def _forest_kernel(x_ref, feat_ref, thr_ref, leaf_ref, out_ref, *,
                   depth: int):
    x = x_ref[...]                                   # (BB, F) float32
    offs = level_offsets(depth)
    cur = jnp.zeros(out_ref.shape, jnp.int32)        # (BB, BT)
    for d in range(depth):
        feat, thr = _select(cur, 2 ** d, offs[d], (feat_ref, thr_ref),
                            (-1, jnp.inf))
        xv = jnp.zeros(cur.shape, jnp.float32)
        for f in range(x.shape[1]):
            xv = jnp.where(feat == f, x[:, f:f + 1], xv)
        # terminal nodes carry feature -1 and threshold +inf: always left
        cur = 2 * cur + jnp.where(xv <= thr, 0, 1)
    (leaf,) = _select(cur, 2 ** depth, 0, (leaf_ref,), (0.0,))
    out_ref[...] = leaf


@functools.partial(
    jax.jit, static_argnames=("depth", "n_trees", "block_b", "interpret"))
def forest_predict_kernel(x, feature, threshold, leaf, *, depth: int,
                          n_trees: int, block_b: int, interpret: bool):
    """Mean leaf value over the ``n_trees`` real trees, (B,) float32.

    x: (B, F) float32. feature/threshold: (level_offsets(depth)[-1], Tp)
    and leaf: (leaf_rows(depth), Tp), Tp a multiple of 128; padded trees
    hold leaf value 0 (``ops.pack_tables`` builds all three)."""
    B, F = x.shape
    rows, Tp = feature.shape
    assert rows == level_offsets(depth)[-1], (rows, depth)
    assert leaf.shape == (leaf_rows(depth), Tp), (leaf.shape, depth)
    assert Tp % LANES == 0, Tp
    bb = min(block_b, _round_up(B, SUBLANES))
    Bp = _round_up(B, bb)
    x = jnp.pad(x, ((0, Bp - B), (0, 0)))
    per_tree = pl.pallas_call(
        functools.partial(_forest_kernel, depth=depth),
        grid=(Tp // LANES, Bp // bb),
        in_specs=[
            pl.BlockSpec((bb, F), lambda t, i: (i, 0)),
            pl.BlockSpec((rows, LANES), lambda t, i: (0, t)),
            pl.BlockSpec((rows, LANES), lambda t, i: (0, t)),
            pl.BlockSpec((leaf.shape[0], LANES), lambda t, i: (0, t)),
        ],
        out_specs=pl.BlockSpec((bb, LANES), lambda t, i: (i, t)),
        out_shape=jax.ShapeDtypeStruct((Bp, Tp), jnp.float32),
        interpret=interpret,
        name="forest_predict",
    )(x, feature, threshold, leaf)
    return per_tree[:B].sum(axis=1) / n_trees
