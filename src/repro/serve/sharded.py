"""Tree-axis device partitioning: the ``ShardedForestEngine``.

The forest's prediction is a MEAN over trees, so the stacked dense tree
arrays (T, N) partition cleanly along the tree axis: each shard owns a
contiguous block of trees, computes its partial leaf-value SUM, and the
engine combines ``sum(partial sums) / n_real_trees``. Inert padding trees
(threshold +inf, value 0) contribute exactly 0 to the sum, so uneven tree
counts cost nothing in accuracy.

Two placements, picked automatically:

  * ``mesh`` — with >= n_shards JAX devices, the dense arrays are laid out
    with ``jax.sharding`` (1-D mesh over the tree axis) and one jitted
    ``jax.shard_map`` call traverses every shard in parallel, combining
    partials with ``lax.psum`` across the mesh. This is the TPU-pod path.
  * ``loop`` — otherwise (e.g. this CPU container, or forced shard counts
    for testing) each shard's block is placed round-robin over the available
    devices and dispatched as its own async jit / Pallas call; XLA overlaps
    the per-device work, Python only collects the partials.

Per-shard compute reuses the existing inference stack unchanged:
``core/forest_jax.dense_leaf_sum`` (the dense-jax traversal core) or the
Pallas forest kernel (``kernels/forest``) when ``use_pallas=True``.

``ShardedForestEngine`` subclasses ``ForestEngine``, so micro-batching, the
feature cache, EngineStats, and hot-swap (``swap_estimator`` rebuilds the
partitioned arrays off-lock and swaps atomically) all behave identically to
the single-device engine — it is a drop-in ``ServingEngine``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..core.forest import ExtraTreesRegressor
from ..core.forest_jax import DenseForest, dense_leaf_sum, to_dense
from .backend import PredictorBackend, pad_pow2
from .engine import EngineConfig, ForestEngine

__all__ = ["ShardedForestEngine", "ShardedForestPredictor"]


@partial(jax.jit, static_argnames=("depth",))
def _leaf_sum_jit(feature, threshold, value, x, *, depth: int):
    return dense_leaf_sum(feature, threshold, value, x, depth)


def _shard_bounds(n_trees: int, n_shards: int) -> list[tuple[int, int]]:
    """Balanced contiguous blocks (sizes differ by at most one, none empty)."""
    splits = np.array_split(np.arange(n_trees), n_shards)
    return [(int(s[0]), int(s[-1]) + 1) for s in splits]


class ShardedForestPredictor:
    """PredictorBackend that partitions one dense forest across shards.

    Shard failure: ``without_shard(i)`` returns a NEW predictor over the
    surviving shards only — the mean renormalizes over the surviving trees
    (``sum(surviving partials) / n_live``), so predictions keep flowing with
    a bounded, countable accuracy degradation instead of an outage. The
    degraded predictor always uses the loop placement (a mesh with a dead
    member cannot dispatch); a later ``swap_estimator`` rebuilds the full
    partitioning.
    """

    def __init__(self, est: ExtraTreesRegressor, *, n_shards: int,
                 dense_depth: int = 10, use_pallas: bool = False,
                 force_loop: bool = False):
        if not est.trees_:
            raise ValueError("estimator is not fitted")
        n_trees = len(est.trees_)
        if n_shards < 1:
            raise ValueError(f"n_shards must be >= 1, got {n_shards}")
        n_shards = min(n_shards, n_trees)      # every shard owns >= 1 tree
        eff_depth = min(dense_depth, max(t.depth() for t in est.trees_))
        dense = to_dense(est, depth=max(eff_depth, 1))

        self.n_trees = n_trees
        self.n_shards = n_shards
        self.depth = dense.depth
        self.use_pallas = use_pallas
        self.devices = jax.devices()
        self.bounds = _shard_bounds(n_trees, n_shards)
        self.shard_sizes = [b - a for a, b in self.bounds]
        self.dead: frozenset[int] = frozenset()
        self.n_live = n_trees
        self._dense = dense            # kept for shard-drop rebuilds

        mesh_capable = (n_shards > 1 and len(self.devices) >= n_shards
                        and not use_pallas and not force_loop)
        self.placement = "mesh" if mesh_capable else "loop"
        if self.placement == "mesh":
            self._build_mesh(dense)
        else:
            self._build_loop(dense)

    @property
    def name(self) -> str:
        kind = "pallas" if self.use_pallas else "dense"
        base = f"sharded-{kind}-{self.placement}x{self.n_shards}"
        return f"{base}-deg{len(self.dead)}" if self.dead else base

    @property
    def shard_devices(self) -> list:
        """The device holding each live shard's trees, in shard order."""
        if self.placement == "mesh":
            shards = sorted(self._arrays[0].addressable_shards,
                            key=lambda s: s.index[0].start)
            return [s.device for s in shards]
        return [dev for _, dev, _ in self._shards]

    # --------------------------------------------------------- shard failure

    def live_tree_indices(self) -> list[int]:
        """Tree indices still contributing to the mean (surviving shards)."""
        return [t for i, (a, b) in enumerate(self.bounds)
                if i not in self.dead for t in range(a, b)]

    def without_shard(self, idx: int) -> "ShardedForestPredictor":
        """A new predictor serving the surviving shards only.

        The dropped shard's trees leave the mean entirely (renormalized
        denominator), so the result equals the tree-walk oracle over the
        surviving trees. The original is left untouched — the engine swaps
        the degraded predictor in atomically under its own lock.
        """
        if not 0 <= idx < self.n_shards:
            raise ValueError(f"shard index {idx} out of range "
                             f"[0, {self.n_shards})")
        if idx in self.dead:
            raise ValueError(f"shard {idx} is already dropped")
        dead = self.dead | {idx}
        if len(dead) >= self.n_shards:
            raise RuntimeError("cannot drop the last surviving shard")
        p = object.__new__(ShardedForestPredictor)
        p.n_trees = self.n_trees
        p.n_shards = self.n_shards
        p.depth = self.depth
        p.use_pallas = self.use_pallas
        p.devices = self.devices
        p.bounds = self.bounds
        p.shard_sizes = [b - a for i, (a, b) in enumerate(self.bounds)
                         if i not in dead]
        p.dead = frozenset(dead)
        p.n_live = sum(b - a for i, (a, b) in enumerate(self.bounds)
                       if i not in dead)
        p._dense = self._dense
        p.placement = "loop"           # a holed mesh cannot dispatch
        p._build_loop(self._dense)
        return p

    # -------------------------------------------------------------- mesh path

    def _build_mesh(self, dense: DenseForest) -> None:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        # equal-size shards for the mesh: pad to S * ceil(T/S) inert trees,
        # laid out so shard i's real trees land in its block
        ts = -(-self.n_trees // self.n_shards)
        Tp = ts * self.n_shards
        N = dense.n_nodes
        feat = np.zeros((Tp, N), dtype=np.int32)
        thr = np.full((Tp, N), np.float32(np.inf))
        val = np.zeros((Tp, N), dtype=np.float32)
        for i, (a, b) in enumerate(self.bounds):
            feat[i * ts:i * ts + (b - a)] = dense.feature[a:b]
            thr[i * ts:i * ts + (b - a)] = dense.threshold[a:b]
            val[i * ts:i * ts + (b - a)] = dense.value[a:b]

        mesh = Mesh(np.asarray(self.devices[:self.n_shards]), ("trees",))
        tree_sharded = NamedSharding(mesh, P("trees", None))
        self._arrays = tuple(jax.device_put(a, tree_sharded)
                             for a in (feat, thr, val))
        depth, n_trees = self.depth, self.n_trees

        def per_shard(x, f, t, v):
            # each device traverses its (ts, N) block; psum combines the
            # partial leaf sums across the tree mesh
            return jax.lax.psum(
                dense_leaf_sum(f, t, v, x, depth, axis_name="trees"), "trees")

        fn = jax.shard_map(per_shard, mesh=mesh,
                           in_specs=(P(), P("trees", None), P("trees", None),
                                     P("trees", None)),
                           out_specs=P())
        self._mesh_fn = jax.jit(lambda x, f, t, v: fn(x, f, t, v) / n_trees)

    # -------------------------------------------------------------- loop path

    def _build_loop(self, dense: DenseForest) -> None:
        # round-robin shard blocks over whatever devices exist; jit dispatch
        # is async, so per-device work overlaps even though Python drives
        # the loop
        from ..kernels.forest import PallasForest

        self._shards = []
        for i, (a, b) in enumerate(self.bounds):
            if i in self.dead:
                continue
            dev = self.devices[i % len(self.devices)]
            block = (dense.feature[a:b], dense.threshold[a:b],
                     dense.value[a:b])
            if self.use_pallas:
                run = PallasForest(*block, self.depth, device=dev)
            else:
                arrays = tuple(jax.device_put(np.ascontiguousarray(arr), dev)
                               for arr in block)
                run = partial(_leaf_sum_jit, *arrays, depth=self.depth)
            self._shards.append((run, dev, b - a))

    def _loop_call(self, x: jax.Array) -> np.ndarray:
        # one input transfer per unique device, not per shard
        x_on = {}
        for _, dev, _ in self._shards:
            if dev not in x_on:
                x_on[dev] = jax.device_put(x, dev)
        # the Pallas kernel returns the shard MEAN (it divides by its real
        # tree count): rescale to a partial sum
        partials = [(run(x_on[dev]), size if self.use_pallas else 1)
                    for run, dev, size in self._shards]
        total = np.zeros(x.shape[0], dtype=np.float64)
        for part, scale in partials:       # collect AFTER all dispatches
            total += np.asarray(part, dtype=np.float64) * scale
        return total / self.n_live         # == n_trees unless shards dropped

    # ------------------------------------------------------------------ call

    def __call__(self, X) -> np.ndarray:
        x = jnp.asarray(X, dtype=jnp.float32)
        if self.placement == "mesh":
            out = self._mesh_fn(x, *self._arrays)
            return np.asarray(out, dtype=np.float64)
        return self._loop_call(x)


class ShardedForestEngine(ForestEngine):
    """ForestEngine whose backend partitions the forest across JAX devices.

    ``n_shards`` defaults to the number of visible devices; pass an explicit
    value to force a partitioning (e.g. ``n_shards=4`` on a 1-CPU host runs
    four logical shards — the correctness tests do exactly this). Everything
    else — micro-batching, caching, stats, hot-swap — is inherited.
    """

    def __init__(self, est: ExtraTreesRegressor,
                 config: EngineConfig | None = None, *,
                 n_shards: int | None = None, use_pallas: bool = False,
                 force_loop: bool = False,
                 calibration_X: np.ndarray | None = None, **overrides):
        backend = overrides.get("backend", (config or EngineConfig()).backend)
        if backend != "auto":
            raise ValueError(
                f"ShardedForestEngine always serves its partitioned path; "
                f"an explicit backend={backend!r} cannot be honored — use a "
                f"plain ForestEngine for that")
        self.n_shards = n_shards if n_shards is not None else max(
            len(jax.devices()), 1)
        self.use_pallas = use_pallas
        self.force_loop = force_loop
        super().__init__(est, config, calibration_X=calibration_X,
                         **overrides)

    def _build(self, est: ExtraTreesRegressor) -> dict[str, PredictorBackend]:
        predictor = ShardedForestPredictor(
            est, n_shards=self.n_shards,
            dense_depth=self.config.dense_depth,
            use_pallas=self.use_pallas,
            force_loop=self.force_loop)
        return {predictor.name: pad_pow2(predictor)}

    # placement metadata reflects the INSTALLED predictor (committed under
    # the engine lock), never one mid-build or from a failed swap
    @property
    def _installed(self) -> ShardedForestPredictor:
        return self.predictor.__wrapped__

    @property
    def placement(self) -> str:
        return self._installed.placement

    @property
    def shard_sizes(self) -> list[int]:
        return self._installed.shard_sizes

    @property
    def shard_devices(self) -> list:
        return self._installed.shard_devices

    @property
    def dead_shards(self) -> frozenset[int]:
        return self._installed.dead

    @property
    def live_trees(self) -> int:
        return self._installed.n_live

    def live_tree_indices(self) -> list[int]:
        return self._installed.live_tree_indices()

    # --------------------------------------------------------- shard failure

    def drop_shard(self, idx: int) -> int:
        """Drop a dead shard; predictions keep flowing from the survivors.

        The forest mean renormalizes over the surviving trees (matching the
        tree-walk oracle restricted to ``live_tree_indices()``), the feature
        cache is invalidated (a degraded model answers differently), the
        generation bumps so in-flight batches of the full forest cannot
        write back stale cache entries, and ``stats.shard_drops`` /
        ``stats.trees_lost`` count the accuracy degradation. Returns the
        number of trees lost. A later ``swap_estimator`` (e.g. from the
        refresher) rebuilds the full partitioning and clears the
        degradation.

        Shard indices are POSITIONS IN THE ORIGINAL PARTITIONING (stable
        across drops): after ``drop_shard(0)`` on a 3-shard engine the
        survivors are shards 1 and 2.
        """
        while True:
            # rebuild over the survivors OFF the engine lock (serving never
            # stalls on the rebuild), then commit atomically — same
            # discipline as swap_estimator
            base = self._installed
            degraded = base.without_shard(idx)
            fn = pad_pow2(degraded)
            with self._cond:
                if self._closed:
                    raise RuntimeError("engine is closed")
                if self._installed is not base:
                    continue           # a swap/drop raced us; rederive
                lost = base.n_live - degraded.n_live
                self._backends = {degraded.name: fn}
                self.backend = degraded.name
                self._predict_fn = fn
                self._cache.clear()
                self._generation += 1
                self.stats.generation = self._generation
                self.stats.shard_drops += 1
                self.stats.trees_lost += lost
                return lost
