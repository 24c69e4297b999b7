"""GQA attention with RoPE/M-RoPE, optional QKV bias, KV-cache decode.

Three entry points sharing one weight layout:
  * ``attend_train``   — full causal self-attention (no cache)
  * ``attend_prefill`` — causal + returns the populated KV cache
  * ``attend_decode``  — 1-token step against a fixed-size cache

The math path is jnp einsum attention by default (XLA fuses it well on TPU);
``cfg.use_pallas`` switches prefill/train to the flash kernel
(kernels/attention, interpret on CPU).
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from ..sharding.context import constrain, current_ctx
from .common import EMBED, HEAD_DIM, HEADS, KV_HEADS, ParamSpec, apply_rope


def attn_specs(cfg) -> dict:
    d, H, Hkv, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    specs = {
        "wq": ParamSpec((d, H, Dh), (EMBED, HEADS, HEAD_DIM)),
        "wk": ParamSpec((d, Hkv, Dh), (EMBED, KV_HEADS, HEAD_DIM)),
        "wv": ParamSpec((d, Hkv, Dh), (EMBED, KV_HEADS, HEAD_DIM)),
        "wo": ParamSpec((H, Dh, d), (HEADS, HEAD_DIM, EMBED)),
    }
    if cfg.qkv_bias:
        specs["bq"] = ParamSpec((H, Dh), (HEADS, HEAD_DIM), init="zeros")
        specs["bk"] = ParamSpec((Hkv, Dh), (KV_HEADS, HEAD_DIM), init="zeros")
        specs["bv"] = ParamSpec((Hkv, Dh), (KV_HEADS, HEAD_DIM), init="zeros")
    return specs


def _qkv(cfg, p, x):
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(dt))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    q = constrain(q, ("act_batch", "act_seq", "act_heads", None))
    kv_axes = ("act_batch", "act_seq", "act_kv_heads", None)
    ctx = current_ctx()
    if ctx is not None:
        # context-parallel fallback (§Perf hillclimb A): when neither the
        # q- nor kv-head count divides the model axis, GSPMD's head_dim
        # sharding partial-sums the (b,h,g,q,k) SCORE tensor — the dominant
        # collective. Sharding the KV sequence instead costs only the tiny
        # softmax partials + the (b,q,h,d) output reduction, and matches
        # the seq-sharded ("cache_seq") KV-cache layout.
        msize = ctx[0].shape.get("model", 1)
        if (msize > 1 and cfg.n_kv_heads % msize and cfg.n_heads % msize
                and k.shape[1] % msize == 0):
            kv_axes = ("act_batch", "act_kv_seq", "act_kv_heads", None)
    k = constrain(k, kv_axes)
    v = constrain(v, kv_axes)
    return q, k, v


Q_CHUNK = 512   # query-chunked attention: caps the f32 score buffer at
                # (B, Hkv, g, Q_CHUNK, Skv) instead of the full S^2


def _sdpa_block(cfg, qg, k, v, *, causal: bool, q_offset, kv_valid_len,
                scale):
    """qg (B,qc,Hkv,g,Dh); k/v (B,Skv,Hkv,Dh) — all in the compute dtype.
    Matmuls stay in the storage dtype (bf16 on TPU) with f32 ACCUMULATION
    (preferred_element_type); softmax/masking in f32. Upcasting K/V to f32
    here would make XLA materialize an f32 copy of the whole KV cache (a
    hoisted convert) — 2x cache memory at decode."""
    Skv = k.shape[1]
    qc = qg.shape[1]
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        qi = jnp.arange(qc)[:, None] + q_offset
        ki = jnp.arange(Skv)[None, :]
        s = jnp.where(qi >= ki, s, -1e30)
    if kv_valid_len is not None:
        ki = jnp.arange(Skv)
        s = jnp.where(ki[None, None, None, None, :] < kv_valid_len, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32)


def _sdpa(cfg, q, k, v, *, causal: bool, q_offset: int = 0,
          kv_valid_len=None):
    """q (B,Sq,H,Dh); k/v (B,Skv,Hkv,Dh). Grouped attention; queries
    processed in chunks of Q_CHUNK (exact — softmax is per-query over the
    full key range) so the score buffer never materializes S^2."""
    B, Sq, H, Dh = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    g = H // Hkv
    scale = 1.0 / jnp.sqrt(jnp.asarray(Dh, jnp.float32))
    qg = q.reshape(B, Sq, Hkv, g, Dh).astype(k.dtype)
    kf = k
    vf = v

    if Sq <= Q_CHUNK or Sq % Q_CHUNK != 0:
        o = _sdpa_block(cfg, qg, kf, vf, causal=causal, q_offset=q_offset,
                        kv_valid_len=kv_valid_len, scale=scale)
        return o.reshape(B, Sq, H, Dh).astype(q.dtype)

    n = Sq // Q_CHUNK
    qs = jnp.moveaxis(qg.reshape(B, n, Q_CHUNK, Hkv, g, Dh), 1, 0)

    def body(_, args):
        i, q_blk = args
        o = _sdpa_block(cfg, q_blk, kf, vf, causal=causal,
                        q_offset=q_offset + i * Q_CHUNK,
                        kv_valid_len=kv_valid_len, scale=scale)
        return (), o

    # checkpoint the chunk body: without it, scan's backward stacks every
    # chunk's softmax probs — re-materializing the full S^2 score buffer the
    # chunking exists to avoid.
    body = jax.checkpoint(body, policy=None, prevent_cse=False)
    _, os = jax.lax.scan(body, (), (jnp.arange(n), qs))
    o = jnp.moveaxis(os, 0, 1).reshape(B, Sq, Hkv, g, Dh)
    return o.reshape(B, Sq, H, Dh).astype(q.dtype)


def attend_train(cfg, p, x, cos, sin):
    q, k, v = _qkv(cfg, p, x)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    if cfg.use_pallas:
        from ..kernels.attention import flash_attention
        o = flash_attention(q.swapaxes(1, 2), k.swapaxes(1, 2),
                            v.swapaxes(1, 2), causal=True).swapaxes(1, 2)
    else:
        o = _sdpa(cfg, q, k, v, causal=True)
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(x.dtype))
    return constrain(out, ("act_batch", "act_seq", "act_embed"))


def attend_prefill(cfg, p, x, cos, sin):
    """Returns (out, (k_cache, v_cache)) — caches in activation dtype."""
    q, k, v = _qkv(cfg, p, x)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    o = _sdpa(cfg, q, k, v, causal=True)
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(x.dtype))
    return out, (k, v)


def attend_decode(cfg, p, x, cos, sin, cache, pos):
    """x (B,1,d); cache (k,v) each (B,Smax,Hkv,Dh); pos scalar int32.
    Returns (out, new_cache)."""
    # barrier: stops XLA:CPU from hoisting this layer's bf16->f32 dot-operand
    # convert across the WHOLE stacked cache (an f32 copy of every layer's
    # cache at once). TPU's MXU consumes bf16 natively — no convert at all.
    k_cache, v_cache = jax.lax.optimization_barrier(cache)
    q, k, v = _qkv(cfg, p, x)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k.astype(k_cache.dtype), pos, axis=1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, v.astype(v_cache.dtype), pos, axis=1)
    o = _sdpa(cfg, q, k_cache, v_cache, causal=False, kv_valid_len=pos + 1)
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(x.dtype))
    # second barrier: keep the RETURNED (bf16) cache distinct from the copy
    # the dot consumes, or XLA:CPU CSEs them and stacks the scan output in
    # f32 (2x cache memory). No-op on TPU.
    return out, jax.lax.optimization_barrier((k_cache, v_cache))


def attend_cross(cfg, p, x, kv_cache):
    """Cross-attention against precomputed encoder K/V (whisper decoder)."""
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dt)
    k, v = kv_cache
    o = _sdpa(cfg, q, k, v, causal=False)
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(dt))


def cross_kv(cfg, p, enc_out):
    dt = enc_out.dtype
    k = jnp.einsum("bsd,dhk->bshk", enc_out, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", enc_out, p["wv"].astype(dt))
    if cfg.qkv_bias:
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    return k, v


def kv_cache_shape(cfg, batch: int, max_len: int):
    Hkv, Dh = cfg.n_kv_heads, cfg.resolved_head_dim
    return (batch, max_len, Hkv, Dh)
