"""The ``flat-jax`` program's two bodies: the level walk over
``pack_levels``' tables (a TPU's) against the gather walk (every other
platform's), run here on the CPU, with the platform patched where a test
needs the TPU's choice."""
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import forest_jax as fj
from repro.core import platform
from repro.core.forest import ExtraTreesRegressor, FlatForest, Tree
from repro.serve import ForestEngine

FEATURES = 12


def _one_leaf(value: float) -> Tree:
    z = np.zeros(1, dtype=np.int32)
    return Tree(feature=z - 1, threshold=np.zeros(1, np.float32),
                left=z - 1, right=z - 1, value=np.full(1, value, np.float32),
                n_samples=z + 1, impurity=np.zeros(1, np.float32))


def _fit(seed: int, n_trees: int, max_depth=None) -> ExtraTreesRegressor:
    rng = np.random.default_rng(seed)
    X = rng.lognormal(1, 1.5, size=(160, FEATURES)).astype(np.float32)
    y = np.log(2 * X[:, 0] + 0.5 * X[:, 3] + 3) + 0.1 * rng.normal(size=160)
    return ExtraTreesRegressor(n_estimators=n_trees, max_depth=max_depth,
                               seed=seed).fit(X, y)


@pytest.fixture(scope="module")
def est():
    """Unbounded trees, trees cut at depths 1 and 3, and a one-leaf tree."""
    est = _fit(1, 6)                  # its widest level, 52, pads to 56
    est.trees_ += (_fit(2, 1, max_depth=1).trees_
                   + _fit(3, 2, max_depth=3).trees_)
    est.trees_.insert(2, _one_leaf(-1.25))
    est.n_estimators = len(est.trees_)
    return est


@pytest.fixture(scope="module")
def flat(est):
    return est.to_flat()


def _depths(tree: Tree) -> np.ndarray:
    depth = np.zeros(tree.n_nodes, dtype=np.int64)
    for i in range(tree.n_nodes):          # parents precede children
        if tree.feature[i] >= 0:
            depth[[tree.left[i], tree.right[i]]] = depth[i] + 1
    return depth


def _renumber(flat: FlatForest, seed: int) -> FlatForest:
    """The same forest with its nodes stored in a random order."""
    n = flat.feature.size
    perm = np.random.default_rng(seed).permutation(n)   # old -> new index
    inv = np.argsort(perm)                              # new -> old

    def child(a):
        return np.where(a[inv] >= 0, perm[np.maximum(a[inv], 0)], -1)

    return FlatForest(feature=flat.feature[inv], threshold=flat.threshold[inv],
                      left=child(flat.left).astype(np.int32),
                      right=child(flat.right).astype(np.int32),
                      value=flat.value[inv],
                      roots=perm[flat.roots].astype(np.int32),
                      max_depth=flat.max_depth)


def _rows(flat: FlatForest, batch: int, seed: int) -> np.ndarray:
    """Lognormal rows, every other one holding thresholds of the forest
    itself, so that ``x <= threshold`` meets its ties."""
    rng = np.random.default_rng(seed)
    x = rng.lognormal(1, 1.5, size=(batch, FEATURES)).astype(np.float32)
    inner = np.flatnonzero(flat.feature >= 0)
    for b in range(0, batch, 2):
        at = rng.choice(inner, size=FEATURES)
        x[b, flat.feature[at]] = flat.threshold[at]
    return x


# ------------------------------------------------------------------ packing

def test_every_node_is_reached_once_at_its_depth(est, flat):
    feature, threshold, child, value = fj.pack_levels(flat)
    L, W, T = feature.shape
    assert (L, T) == (flat.max_depth + 1, len(est.trees_))
    widest = 0
    for t, tree in enumerate(est.trees_):
        per_level = np.bincount(_depths(tree), minlength=L)
        widest = max(widest, per_level.max())
        reached = np.zeros(L, dtype=np.int64)
        slots = np.array([0])
        for lvl in range(L):
            assert sorted(slots) == list(range(per_level[lvl])), (t, lvl)
            reached[lvl] = slots.size
            inner = slots[feature[lvl, slots, t] >= 0]
            slots = np.concatenate([child[lvl, inner, t],
                                    child[lvl, inner, t] + 1])
        assert slots.size == 0
        np.testing.assert_array_equal(reached, per_level)
        # what the tables hold is the tree's own content, level by level
        depth = _depths(tree)
        for lvl in range(L):
            here = depth == lvl
            used = slice(0, per_level[lvl])
            inner = tree.feature[here] >= 0
            held = feature[lvl, used, t] >= 0
            assert sorted(feature[lvl, used, t][held]) \
                == sorted(tree.feature[here][inner])
            assert sorted(threshold[lvl, used, t][held]) \
                == sorted(tree.threshold[here][inner])
            assert sorted(value[lvl, used, t][~held]) \
                == sorted(tree.value[here][~inner])
            pad = slice(per_level[lvl], W)
            assert (feature[lvl, pad, t] == -1).all()
            for table in (threshold, child, value):
                assert not table[lvl, pad, t].any()
    assert W == -(-widest // 8) * 8


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_layout_does_not_depend_on_node_numbering(flat, seed):
    mixed = _renumber(flat, seed)
    assert not np.array_equal(mixed.roots, flat.roots)
    for a, b in zip(fj.pack_levels(flat), fj.pack_levels(mixed)):
        np.testing.assert_array_equal(a, b)


def test_packing_refuses_a_tree_deeper_than_max_depth(flat):
    short = FlatForest(**{**flat.__dict__, "max_depth": flat.max_depth - 1})
    with pytest.raises(ValueError, match="deeper than max_depth"):
        fj.pack_levels(short)


# -------------------------------------------------------------------- walks

@pytest.mark.parametrize("batch", [1, 7, 64, 300])
def test_level_walk_answers_as_the_gather_walk_does(est, flat, batch):
    x = jnp.asarray(_rows(flat, batch, seed=batch))
    nodes = (flat.feature, flat.threshold, flat.left, flat.right, flat.value,
             flat.roots)
    tables = fj.pack_levels(flat)
    gathers = jax.jit(fj._gather_leaves, static_argnames="max_depth")(
        *nodes, x, max_depth=flat.max_depth)
    levels = jax.jit(fj._level_leaves, static_argnames="max_depth")(
        *tables, x, max_depth=flat.max_depth)
    assert gathers.shape == levels.shape == (batch, len(est.trees_))
    np.testing.assert_array_equal(
        np.asarray(levels).view(np.int32), np.asarray(gathers).view(np.int32))
    one = np.asarray(fj._predict_flat_jax(*nodes, x, max_depth=flat.max_depth))
    two = np.asarray(fj._predict_flat_jax(*tables, x, max_depth=flat.max_depth,
                                          walk="levels"))
    np.testing.assert_array_equal(two, one)
    np.testing.assert_allclose(two, est.predict(np.asarray(x)), rtol=1e-6)


# ------------------------------------------------------- platform and engine

def _as_platform(monkeypatch, kind: str):
    """Let ``FlatForestJax`` see its device as a ``kind`` device."""
    monkeypatch.setattr(fj, "flat_walk", lambda device: platform.flat_walk(
        SimpleNamespace(platform=kind)))


@pytest.mark.parametrize("kind,walk", [("tpu", "levels"), ("cpu", "gathers"),
                                       ("gpu", "gathers")])
def test_platform_picks_the_walk(kind, walk):
    assert platform.flat_walk(SimpleNamespace(platform=kind)) == walk


def test_the_walk_defaults_to_the_first_device():
    want = "levels" if jax.devices()[0].platform == "tpu" else "gathers"
    assert platform.flat_walk() == want


@pytest.mark.parametrize("kind,walk,n_arrays", [("tpu", "levels", 4),
                                                ("cpu", "gathers", 6)])
def test_flat_forest_jax_keeps_only_its_walk_s_arrays(
        monkeypatch, est, flat, kind, walk, n_arrays):
    _as_platform(monkeypatch, kind)
    model = fj.FlatForestJax(flat)
    assert model.walk == walk
    assert len(model.arrays) == n_arrays
    if walk == "levels":
        assert model.arrays[0].shape[::2] == (flat.max_depth + 1,
                                              len(est.trees_))
    x = _rows(flat, 33, seed=5)
    np.testing.assert_allclose(np.asarray(model(x)), est.predict(x),
                               rtol=1e-6)


@pytest.mark.parametrize("backend,kind,counts", [
    ("flat-jax", "tpu", True), ("flat-jax", "cpu", False),
    ("flat-numpy", "tpu", False)])
def test_engine_counts_the_rows_the_level_walk_took(
        monkeypatch, est, flat, backend, kind, counts):
    from repro.obs.registry import MetricsRegistry
    _as_platform(monkeypatch, kind)
    with ForestEngine(est, backend=backend, cache_size=0) as engine:
        registry = MetricsRegistry()
        engine.register_metrics(registry)
        for n in (5, 8, 1, 12):
            engine.predict(_rows(flat, n, seed=n))
        engine.swap_estimator(est)            # a swap reads the walk anew
        engine.predict(_rows(flat, 3, seed=3))
        st = engine.stats_snapshot()
        got = {r["name"]: r["value"] for r in registry.snapshot()}
    assert st.backend_rows == 5 + 8 + 1 + 12 + 3
    want = st.backend_rows + st.padded_rows if counts else 0
    assert st.level_walk_rows == want
    assert got["engine.level_walk_rows"] == want
