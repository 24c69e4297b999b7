"""The forest's work count and the peaks table."""
import numpy as np
import pytest

from perfbench import forest as pf
from perfbench import run, work


def three_nodes():
    return pf.Forest(feature=np.array([0, -1, -1], np.int32),
                     threshold=np.array([0.5, 0, 0], np.float32),
                     left=np.array([1, -1, -1], np.int32),
                     right=np.array([2, -1, -1], np.int32),
                     value=np.array([2.0, 1.0, 3.0], np.float32),
                     n_samples=np.array([2, 1, 1], np.int32),
                     impurity=np.zeros(3, np.float32),
                     offsets=np.array([0, 3]), n_features=1)


def test_hand_built_tree():
    f = three_nodes()
    X = np.array([[0.2], [0.9]], np.float32)
    np.testing.assert_array_equal(work.flat_paths(f, X), [1, 1])
    np.testing.assert_array_equal(pf.walk(f, X), [1.0, 3.0])
    ops, bw = work.peaks("TPU v5 lite")
    # 3 node records, 2 rows of 1 feature in, 2 results out: bytes bound
    want = (3 * work.NODE_BYTES + 2 * (4 + work.RESULT_BYTES)) / bw
    assert work.least_seconds(2, 1, 1.0, 3, 1, "TPU v5 lite") == want
    assert want > 2 / ops


def test_flat_and_dense_layouts_count_the_same():
    from repro.core.forest import ExtraTreesRegressor, Tree
    from repro.core.forest_jax import to_dense
    X = run.load_catalog()
    f = pf.fit(X, run.catalog_targets(), 6, 6, np.random.default_rng(3))
    est = ExtraTreesRegressor(n_estimators=f.n_trees)
    est.trees_ = [Tree(**f.tree(t)) for t in range(f.n_trees)]
    est.n_features_ = f.n_features
    dense = to_dense(est, depth=6)
    np.testing.assert_array_equal(
        work.dense_paths(dense.feature, dense.threshold, X),
        work.flat_paths(f, X))
    assert work.dense_nodes(dense.feature) == len(f.feature)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        work.peaks("TPU v99")
