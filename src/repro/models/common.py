"""Shared model machinery: parameter specs with logical sharding axes,
initialization, norms, rotary embeddings (incl. M-RoPE).

Parameters are declared once as ``ParamSpec`` pytrees (shape + logical axes +
init); materialization (``init_params``) and sharding (``sharding/rules.py``
maps logical axes -> mesh axes) both read the same declaration, so a model
definition is automatically shardable under any strategy.
"""
from __future__ import annotations

import hashlib
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------- param specs

# logical axis vocabulary (see sharding/rules.py for mesh mappings)
BATCH, SEQ, EMBED, MLP, HEADS, KV_HEADS, HEAD_DIM, VOCAB, EXPERT = (
    "batch", "seq", "embed", "mlp", "heads", "kv_heads", "head_dim",
    "vocab", "expert")
LAYERS, INNER, STATE, CONV, LORA = "layers", "inner", "state", "conv", "lora"


@dataclass(frozen=True)
class ParamSpec:
    shape: tuple
    axes: tuple                    # logical axis per dim (None = replicated)
    init: str = "normal"           # normal | zeros | ones | embed
    scale: float | None = None     # None -> 1/sqrt(fan_in)
    dtype: str = "float32"

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def _leaf_key(root: jax.Array, path: str) -> jax.Array:
    h = int.from_bytes(hashlib.md5(path.encode()).digest()[:4], "little")
    return jax.random.fold_in(root, h)


def _path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


def init_params(specs, key: jax.Array):
    """Materialize a ParamSpec pytree. Per-leaf keys derive from the tree
    path (stable under refactors that keep names)."""
    def make(path, spec: ParamSpec):
        k = _leaf_key(key, _path_str(path))
        dt = jnp.dtype(spec.dtype)
        if spec.init == "zeros":
            return jnp.zeros(spec.shape, dt)
        if spec.init == "ones":
            return jnp.ones(spec.shape, dt)
        fan_in = spec.shape[0] if len(spec.shape) >= 2 else max(spec.shape[-1], 1)
        if spec.init == "embed":
            scale = spec.scale if spec.scale is not None else 1.0
        else:
            scale = spec.scale if spec.scale is not None else 1.0 / np.sqrt(fan_in)
        return (jax.random.normal(k, spec.shape, jnp.float32) * scale).astype(dt)

    return jax.tree_util.tree_map_with_path(
        make, specs, is_leaf=lambda x: isinstance(x, ParamSpec))


def abstract_params(specs):
    """ShapeDtypeStruct pytree (for dry-run lowering without allocation)."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.dtype(s.dtype)),
        specs, is_leaf=lambda x: isinstance(x, ParamSpec))


def logical_axes(specs):
    """Pytree of logical-axes tuples, same structure as the params."""
    return jax.tree.map(lambda s: s.axes, specs,
                        is_leaf=lambda x: isinstance(x, ParamSpec))


def stack_specs(specs, n: int, axis_name: str = LAYERS):
    """Prepend a layer axis to every leaf (scan-over-layers storage)."""
    return jax.tree.map(
        lambda s: ParamSpec((n,) + s.shape, (axis_name,) + s.axes,
                            s.init, s.scale, s.dtype),
        specs, is_leaf=lambda x: isinstance(x, ParamSpec))


# ------------------------------------------------------------------- numerics

def rms_norm(x, w, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w.astype(x.dtype)


def layer_norm(x, w, b, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = xf.mean(axis=-1, keepdims=True)
    var = ((xf - mu) ** 2).mean(axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    return out.astype(x.dtype) * w.astype(x.dtype) + b.astype(x.dtype)


def gelu(x):
    return jax.nn.gelu(x, approximate=True)


def silu(x):
    return x * jax.nn.sigmoid(x)


def softplus(x):
    return jax.nn.softplus(x)


# ---------------------------------------------------------------------- rope

def rope_freqs(head_dim: int, theta: float):
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def rope_cos_sin(positions, head_dim: int, theta: float):
    """positions: (..., S) int -> cos/sin (..., S, head_dim/2)."""
    freqs = rope_freqs(head_dim, theta)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin):
    """x: (B, S, H, D); cos/sin: (B, S, D/2) (broadcast over heads).
    Half-rotation (llama-style)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[:, :, None, :].astype(x.dtype)
    s = sin[:, :, None, :].astype(x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def mrope_cos_sin(positions3, head_dim: int, theta: float,
                  sections: tuple[int, int, int]):
    """M-RoPE (qwen2-vl): positions3 (B, S, 3) = (t, h, w) ids; the rotary
    frequency bands are split into ``sections`` (sum = head_dim/2), each band
    driven by its own position channel."""
    assert sum(sections) == head_dim // 2, (sections, head_dim)
    freqs = rope_freqs(head_dim, theta)                     # (D/2,)
    ang_txy = positions3.astype(jnp.float32)[..., None, :] * freqs[None, None, :, None]
    # ang_txy: (B, S, D/2, 3); select the driving channel per band
    sel = jnp.repeat(jnp.arange(3), jnp.asarray(sections), total_repeat_length=head_dim // 2)
    ang = jnp.take_along_axis(ang_txy, sel[None, None, :, None], axis=-1)[..., 0]
    return jnp.cos(ang), jnp.sin(ang)


def causal_mask(sq: int, skv: int, offset: int = 0):
    qi = jnp.arange(sq)[:, None] + offset
    ki = jnp.arange(skv)[None, :]
    return qi >= ki                                          # (Sq, Skv) bool


def cross_entropy_loss(logits, labels, z_loss: float = 1e-4):
    """Mean next-token CE in f32 with optional z-loss (stabilizes the huge
    vocab heads at scale). logits (B, S, V), labels (B, S)."""
    lf = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    ce = (lse - gold).mean()
    if z_loss:
        ce = ce + z_loss * (lse ** 2).mean()
    return ce
