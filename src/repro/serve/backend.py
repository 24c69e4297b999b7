"""Backend layer of the serving stack: the ``PredictorBackend`` protocol and
the builders that turn one fitted forest into concrete inference callables.

Extracted from ``serve/engine.py`` so that engines (``ForestEngine``,
``ShardedForestEngine``) and anything else that wants a raw inference path
share ONE contract:

  * ``PredictorBackend`` — a callable ``(B, F) float32 -> (B,) float`` over a
    FIXED fitted forest. Backends are pure w.r.t. the model: the same X under
    the same backend instance always yields the same y (this is what makes
    the engine's feature-vector cache and the hot-swap generation logic
    sound).
  * ``build_backends`` — constructs every requested path (tree-walk,
    flat-numpy, flat-jax, dense-jax, pallas) for one estimator.
  * ``ServingEngine`` — the engine-level contract the scheduler and the
    refresher duck-type against (predict / predict_async / swap_estimator /
    close / stats). ``cluster.remote.RemoteReplica`` satisfies it too: a
    pool member may live in another process or on another machine.
  * ``DeadlineAwarePredictor`` / ``supports_deadline`` — the optional
    extension for serving tiers: ``predict(X, deadline_s=..., priority=...)``
    lets a caller's remaining deadline slack order the admission queue
    (``core.scheduler.slack_priority``). The scheduler probes for it with
    ``supports_deadline`` and falls back to the plain call.
"""
from __future__ import annotations

import inspect
from typing import Protocol, runtime_checkable

import numpy as np

from ..core.forest import ExtraTreesRegressor, predict_flat
from ..obs.tracing import span

BACKENDS = ("tree-walk", "flat-numpy", "flat-jax", "dense-jax", "pallas")
#: paths that embed the trees in a complete tree of at most ``dense_depth``
TRUNCATING = ("dense-jax", "pallas")


@runtime_checkable
class PredictorBackend(Protocol):
    """One inference path over one fixed fitted forest."""

    def __call__(self, X: np.ndarray) -> np.ndarray:  # (B, F) -> (B,)
        ...


@runtime_checkable
class ServingEngine(Protocol):
    """What the scheduler / refresher / benchmarks require of an engine."""

    def predict(self, X: np.ndarray) -> np.ndarray: ...

    def swap_estimator(self, est: ExtraTreesRegressor) -> int: ...

    def close(self) -> None: ...


@runtime_checkable
class DeadlineAwarePredictor(Protocol):
    """A predictor whose serving tier can honor urgency: the remaining
    deadline budget rides along with the call (and over the wire as
    ``deadline_ms`` — see ``cluster/transport.py``), and ``priority=None``
    means "derive it from my slack" (``core.scheduler.slack_priority``)."""

    def predict(self, X: np.ndarray, *, deadline_s: float | None = ...,
                priority: int | None = ...) -> np.ndarray: ...


def supports_deadline(fn) -> bool:
    """True when ``fn`` (a ``predict`` method or bare callable) accepts a
    ``deadline_s`` keyword — how ``core.scheduler._predict`` decides whether
    to thread its remaining slack through. Signature inspection, not
    try/except: a TypeError raised INSIDE a predictor must surface, not be
    mistaken for an unsupported keyword."""
    try:
        sig = inspect.signature(fn)
    except (TypeError, ValueError):
        return False                  # builtins/ufuncs: no visible signature
    params = sig.parameters
    if "deadline_s" in params:
        return True
    return any(p.kind is inspect.Parameter.VAR_KEYWORD
               for p in params.values())


def calibration_rows(n_rows: int, n_features: int,
                     seed: int = 0) -> np.ndarray:
    """Feature-shaped rows for timing backends / probing replicas: the
    features are non-negative and heavy-tailed (§3.1); for pure timing the
    distribution is irrelevant, only the shapes are. One definition so the
    engine's auto-calibration and the cluster tier's health probes can
    never drift apart."""
    rng = np.random.default_rng(seed)
    return rng.lognormal(1.0, 1.5,
                         size=(n_rows, n_features)).astype(np.float32)


def pow2_padding(rows: int) -> int:
    """Rows ``pad_pow2`` appends to a batch of ``rows``."""
    return (1 << max(rows - 1, 0).bit_length()) - rows


def pad_pow2(fn: PredictorBackend) -> PredictorBackend:
    """Pad the batch dim to the next power of two before calling ``fn``.

    The jit'd jax paths specialize on batch shape; micro-batch flushes have
    arbitrary sizes, so without padding every new size pays a fresh
    compilation. Pow-2 padding bounds the number of compiled variants to
    log2(max_batch). Padding rows replicate the last sample (any valid row
    works — the pad outputs are sliced off). The wrapper's ``padding``
    (``pow2_padding``) tells the engine that owns it how many rows a call
    appends.
    """
    def wrapped(X):
        B = X.shape[0]
        extra = pow2_padding(B)
        with span("backend.pad", rows=B, padded=extra):
            if extra:
                pad = np.broadcast_to(X[-1:], (extra,) + X.shape[1:])
                X = np.concatenate([X, pad], axis=0)
        with span("backend.launch", rows=B):
            y = fn(X)                  # returns before the chip finishes
        with span("backend.wait", rows=B):
            return np.asarray(y)[:B]
    wrapped.__wrapped__ = fn
    wrapped.padding = pow2_padding
    return wrapped


def build_transfer_engine(device, *, target: str = "time_us", monitor=None,
                          config=None, log_output: bool = False):
    """Serve a device the forests never trained on, IMMEDIATELY.

    Returns a ``core.transfer.TransferPredictor`` — the cold-start hybrid
    (spec-sheet analytical prior, least-squares-refitted per observation,
    with a forest on its log-residuals once ≥ ``config.min_forest_samples``
    probes accumulate). It duck-types the serving surface (``predict`` /
    ``close`` / ``n_features`` / ``stats_snapshot``), so it can:

      * sit in a ``ReplicaPool`` behind ``ClusterFrontend`` like any engine
        (health probes use :func:`calibration_rows`, which it prices fine),
      * fill a device slot in ``MultiDeviceEngine`` — pass
        ``log_output=True`` there, matching ``log_time=True`` forests,
      * graduate into a ``ForestEngine`` later:
        ``engine.swap_estimator(predictor.to_forest())`` once the device
        has enough samples for a full per-device forest.

    ``monitor=`` (a ``CalibrationMonitor``) makes every ``observe(x, y)``
    record the pre-update prediction, so ``calibration.mape{device}`` is
    the live convergence gauge for the new device.

    ``device`` may be a ``DeviceModel``, a known device name, or an UNKNOWN
    name (the generic mid-range prior is used until ``calibrate(device=...)``
    re-targets it).
    """
    from ..core.transfer import TransferPredictor
    return TransferPredictor(device, target=target, config=config,
                             monitor=monitor, log_output=log_output)


def max_depth(est: ExtraTreesRegressor) -> int:
    return max((t.depth() for t in est.trees_), default=0)


def exact_candidates(est: ExtraTreesRegressor, dense_depth: int,
                     candidates=None) -> tuple[str, ...]:
    """The paths auto-selection may choose from: ``candidates`` (default:
    all), less the dense layouts when the trees are deeper than
    ``dense_depth`` — those would serve the truncated forest."""
    names = BACKENDS if candidates is None else tuple(candidates)
    if max_depth(est) > dense_depth:
        names = tuple(n for n in names if n not in TRUNCATING)
    return names


def build_backends(est: ExtraTreesRegressor, *, dense_depth: int = 10,
                   only=None) -> dict[str, PredictorBackend]:
    """{name: fn(X float32 (B,F)) -> (B,) float64} for every requested path.

    ``only=None`` builds every path that is exact for this forest
    (``exact_candidates``). A path named in ``only`` is built as asked:
    ``dense-jax`` and ``pallas`` embed the trees at depth
    ``min(dense_depth, max tree depth)`` and so replace deeper subtrees by
    their mean. A path that fails to build raises.
    """
    names = exact_candidates(est, dense_depth) if only is None else tuple(only)
    for n in names:
        if n not in BACKENDS:
            raise ValueError(f"unknown backend {n!r} (have {BACKENDS})")
    out: dict = {}

    if "tree-walk" in names:
        out["tree-walk"] = lambda X: est.predict(X)

    if "flat-numpy" in names or "flat-jax" in names:
        flat = est.to_flat()
        if "flat-numpy" in names:
            out["flat-numpy"] = lambda X: predict_flat(flat, X)
        if "flat-jax" in names:
            from ..core.forest_jax import FlatForestJax
            out["flat-jax"] = pad_pow2(FlatForestJax(flat))

    if "dense-jax" in names or "pallas" in names:
        from ..core.forest_jax import DenseForestJax, to_dense
        dense = to_dense(est, depth=max(min(dense_depth, max_depth(est)), 1))
        if "dense-jax" in names:
            out["dense-jax"] = pad_pow2(DenseForestJax(dense))
        if "pallas" in names:
            from ..kernels.forest import PallasForest
            out["pallas"] = pad_pow2(PallasForest.from_dense(dense))
    return out
