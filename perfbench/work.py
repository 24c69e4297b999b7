"""What a forest call has to do at the least, and the chip's peaks.

The work is the fitted forest's, whatever layout serves it: a row costs
one compare per internal node on its root-to-leaf path in every tree, and
a call has to read every real node record once (feature, threshold, left,
right and value: 20 bytes), read its rows in and write one result per row
out. So the flat layout, the dense layout and the Pallas kernel's tables
are held to the same work. The least time of a call is the larger of
compares over the peak operation rate and bytes over the peak memory
bandwidth; for these forests the bytes bound it.
"""
from __future__ import annotations

import numpy as np

NODE_BYTES = 20          # int32 feature, left, right; float32 threshold, value
RESULT_BYTES = 4         # one float32 per row

#: Published peaks by ``device_kind``: (operations/s, HBM bytes/s, source).
PEAKS = {
    "TPU v5 lite": (197e12, 819e9,
                    "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                    "bf16, 819 GB/s HBM"),
}


def peaks(device_kind: str) -> tuple[float, float]:
    """(peak operations/s, peak HBM bytes/s); an unknown chip is an error."""
    try:
        ops, bw, _ = PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r}; add them to PEAKS") from None
    return ops, bw


def flat_paths(forest, X: np.ndarray) -> np.ndarray:
    """(N,) compares per row summed over the trees, for a ``forest.Forest``."""
    X = np.ascontiguousarray(X, np.float32)
    total = np.zeros(len(X), np.int64)
    for t in range(forest.n_trees):
        lo = forest.offsets[t]
        cur = np.zeros(len(X), np.int64)
        live = np.arange(len(X))
        while len(live):
            f = forest.feature[lo + cur[live]]
            live, f = live[f >= 0], f[f >= 0]
            node = lo + cur[live]
            total[live] += 1
            go_left = X[live, f] <= forest.threshold[node]
            cur[live] = np.where(go_left, forest.left[node], forest.right[node])
    return total


def dense_paths(feature: np.ndarray, threshold: np.ndarray,
                X: np.ndarray) -> np.ndarray:
    """The same count for a dense layout: (T, 2^(D+1)-1) complete trees,
    children of slot i at 2i+1 and 2i+2, feature -1 where the real tree has
    ended."""
    X = np.ascontiguousarray(X, np.float32)
    T, N = feature.shape
    total = np.zeros(len(X), np.int64)
    for t in range(T):
        cur = np.zeros(len(X), np.int64)
        live = np.arange(len(X))
        while len(live):
            f = feature[t, cur[live]]
            live, f = live[f >= 0], f[f >= 0]
            total[live] += 1
            go_left = X[live, f] <= threshold[t, cur[live]]
            cur[live] = 2 * cur[live] + np.where(go_left, 1, 2)
            live = live[cur[live] < N]
    return total


def dense_nodes(feature: np.ndarray) -> int:
    """Real nodes of a dense layout: internal slots, and one leaf more than
    internal slots per tree."""
    return int(2 * np.count_nonzero(feature >= 0) + feature.shape[0])


def least_seconds(rows: float, calls: float, compares_per_row: float,
                  n_nodes: int, n_features: int,
                  device_kind: str) -> float:
    """Least time of ``calls`` forest calls that answer ``rows`` rows."""
    ops, bw = peaks(device_kind)
    compares = rows * compares_per_row
    moved = (calls * n_nodes * NODE_BYTES
             + rows * (n_features * 4 + RESULT_BYTES))
    return max(compares / ops, moved / bw)
