"""The benchmark's forest: its fit, its plain walk, and the control."""
import json

import numpy as np
import pytest

from perfbench import forest as pf
from perfbench import run

CATALOG = json.loads(run.CATALOG.read_text())
X = np.asarray(CATALOG["X"], np.float32)
Y = np.asarray(CATALOG["y"])


def program_estimator(forest):
    from repro.core.forest import ExtraTreesRegressor, Tree
    est = ExtraTreesRegressor(n_estimators=forest.n_trees)
    est.trees_ = [Tree(**forest.tree(t)) for t in range(forest.n_trees)]
    est.n_features_ = forest.n_features
    return est


def from_program(est):
    """The program's fitted trees as a ``forest.Forest``."""
    trees = est.trees_
    cat = {k: np.concatenate([getattr(t, k) for t in trees])
           for k in pf.FIELDS}
    offsets = np.r_[0, np.cumsum([t.n_nodes for t in trees])]
    return pf.Forest(**cat, offsets=offsets, n_features=est.n_features_)


def test_walk_equals_program_predict_on_its_own_fit():
    from repro.core.forest import ExtraTreesRegressor
    est = ExtraTreesRegressor(n_estimators=8, seed=3).fit(X[:80], Y[:80])
    np.testing.assert_array_equal(pf.walk(from_program(est), X),
                                  est.predict(X))


@pytest.mark.parametrize("max_depth", [None, 4])
def test_program_predict_equals_walk_on_benchmark_fit(max_depth):
    f = pf.fit(X, Y, 8, max_depth, np.random.default_rng(1))
    np.testing.assert_array_equal(program_estimator(f).predict(X),
                                  pf.walk(f, X))


def test_fit_is_seeded():
    a = pf.fit(X, Y, 4, None, np.random.default_rng([5, 0]))
    b = pf.fit(X, Y, 4, None, np.random.default_rng([5, 0]))
    c = pf.fit(X, Y, 4, None, np.random.default_rng([6, 0]))
    for k in (*pf.FIELDS, "offsets"):
        np.testing.assert_array_equal(getattr(a, k), getattr(b, k))
    assert not np.array_equal(a.threshold[:50], c.threshold[:50])


def test_fit_grows_until_pure_and_stops_at_max_depth():
    rows = np.unique(X, axis=0, return_index=True)[1][:60]
    Xu, yu = X[rows], Y[rows]
    deep = pf.fit(Xu, yu, 4, None, np.random.default_rng(2))
    np.testing.assert_allclose(pf.walk(deep, Xu), yu, rtol=1e-6)
    cut = pf.fit(Xu, yu, 4, 3, np.random.default_rng(2))
    assert max(t.depth() for t in program_estimator(cut).trees_) == 3
    leaves = cut.feature < 0
    assert cut.n_samples[leaves].sum() == 4 * len(Xu)


def test_save_load_round_trip(tmp_path):
    f = pf.fit(X, Y, 3, 5, np.random.default_rng(4))
    f.save(tmp_path / "f.npz")
    g = pf.Forest.load(tmp_path / "f.npz")
    np.testing.assert_array_equal(pf.walk(g, X), pf.walk(f, X))
    assert g.n_features == 12


def test_bf16_control_fails_the_limit_and_float32_passes():
    """The control reads far above ``MAX_REL_ERR``; the program's
    arithmetic (float32 leaves averaged in float32) reads far below."""
    f = pf.fit(X, Y, 64, None, np.random.default_rng(7))
    Xp = X * (1 + 1e-3 * np.random.default_rng(8).standard_normal(X.shape))
    Xp = Xp.astype(np.float32)
    want = pf.walk(f, Xp)
    scale = np.maximum(np.abs(want), 1.0)
    control = np.max(np.abs(pf.walk_bf16(f, Xp) - want) / scale)
    assert control > 10 * run.MAX_REL_ERR
    leaves = np.stack([f.value[pf._leaves(f, t, Xp, f.threshold)]
                       for t in range(f.n_trees)])
    f32 = leaves.astype(np.float32).mean(axis=0, dtype=np.float32)
    assert np.max(np.abs(f32 - want) / scale) < run.MAX_REL_ERR / 10
