"""Decoder-only LM: dense (llama/mistral/qwen-style), MoE, and VLM variants.

One block = pre-RMSNorm GQA attention + pre-RMSNorm SwiGLU MLP (or MoE).
Layers are stored stacked (leading ``layers`` axis) and executed with
``lax.scan`` — the HLO contains ONE block body with a while trip count of L,
keeping compile time flat in depth and making the roofline analyzer's
trip-count weighting exact. ``cfg.remat`` wraps the scan body in
``jax.checkpoint`` (policy: save nothing) for activation rematerialization.

The VLM variant (qwen2-vl) prepends projected patch embeddings (the vision
tower is a STUB per the task spec — ``input_specs`` supplies precomputed
patches) and drives attention with M-RoPE 3-channel position ids.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..sharding.context import constrain, constrain_tree
from .attention import (attend_decode, attend_prefill, attend_train,
                        attn_specs, kv_cache_shape)
from .common import (BATCH, EMBED, KV_HEADS, HEAD_DIM, VOCAB, ParamSpec,
                     cross_entropy_loss, mrope_cos_sin, rms_norm,
                     rope_cos_sin, stack_specs)
from .mlp import swiglu, swiglu_specs
from .moe import moe_apply, moe_specs


def block_specs(cfg) -> dict:
    d = cfg.d_model
    s = {
        "ln1": ParamSpec((d,), (EMBED,), init="ones"),
        "attn": attn_specs(cfg),
        "ln2": ParamSpec((d,), (EMBED,), init="ones"),
    }
    if cfg.n_experts:
        s["moe"] = moe_specs(cfg)
    else:
        s["mlp"] = swiglu_specs(cfg)
    return s


def lm_specs(cfg) -> dict:
    d, V = cfg.d_model, cfg.vocab
    s = {
        "embed": ParamSpec((V, d), (VOCAB, EMBED), init="embed", scale=0.02),
        "blocks": stack_specs(block_specs(cfg), cfg.n_layers),
        "ln_f": ParamSpec((d,), (EMBED,), init="ones"),
    }
    if not cfg.tie_embeddings:
        s["lm_head"] = ParamSpec((d, V), (EMBED, VOCAB))
    if cfg.family == "vlm":
        s["patch_proj"] = {
            "w1": ParamSpec((cfg.patch_dim, d), (None, EMBED)),
            "w2": ParamSpec((d, d), (EMBED, EMBED)),
        }
    return s


def _block_apply(cfg, p, x, cos, sin, mode, cache=None, pos=None):
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    new_cache = None
    if mode == "train":
        a = attend_train(cfg, p["attn"], h, cos, sin)
    elif mode == "prefill":
        a, new_cache = attend_prefill(cfg, p["attn"], h, cos, sin)
    else:
        a, new_cache = attend_decode(cfg, p["attn"], h, cos, sin, cache, pos)
    x = x + a
    h = rms_norm(x, p["ln2"], cfg.norm_eps)
    if cfg.n_experts:
        m, aux = moe_apply(cfg, p["moe"], h)
    else:
        m, aux = swiglu(p["mlp"], h), jnp.float32(0.0)
    return x + m, new_cache, aux


def _run_blocks(cfg, params, x, cos, sin, mode, caches=None, pos=None):
    """Scan over stacked layer params; returns (x, new_caches, aux_sum).

    Training with ``cfg.remat_groups = G > 0`` uses a scan-of-scans: the
    outer scan saves one carry per GROUP, the inner (checkpointed) scan
    saves one per layer only transiently during that group's backward —
    peak residual memory drops from O(L) to O(G + L/G) carries (the square-
    root remat schedule). Prefill/decode keep the flat scan (caches)."""
    from .common import logical_axes as _lax
    block_axes = _lax(block_specs(cfg))
    act_dt = jnp.dtype(cfg.dtype)

    def cast_block(tree):
        # cast the layer's f32 master weights to the compute dtype WHILE
        # STILL SHARDED (pinned by constrain_tree): the FSDP all-gather then
        # moves bf16, halving the dominant weight-gather volume (§Perf
        # hillclimb C, iteration 1). The optimization barrier stops
        # XLA:CPU's f32-dot emulation from cancelling the bf16 round-trip
        # (which would silently re-gather f32); it is a no-op on TPU.
        cast = jax.tree.map(
            lambda a: a.astype(act_dt)
            if jnp.issubdtype(a.dtype, jnp.floating) else a, tree)
        return jax.lax.optimization_barrier(cast)

    def body(carry, xs):
        x = carry
        if mode == "decode":
            layer_p, layer_cache = xs
        else:
            layer_p, layer_cache = xs, None
        layer_p = cast_block(constrain_tree(layer_p, block_axes))
        x, new_cache, aux = _block_apply(cfg, layer_p, x, cos, sin, mode,
                                         cache=layer_cache, pos=pos)
        x = constrain(x, ("act_batch", "act_seq", "act_embed"))
        return x, (new_cache, aux)

    remat_policy = None
    if cfg.n_experts:
        # keep the dispatched expert buffers from the forward pass: the
        # backward otherwise re-runs the scatter + all-reduce per choice
        remat_policy = jax.checkpoint_policies.save_only_these_names(
            "moe_buf")

    G = cfg.remat_groups
    if (mode == "train" and cfg.remat and G
            and cfg.n_layers % max(G, 1) == 0 and G < cfg.n_layers):
        inner = cfg.n_layers // G
        grouped = jax.tree.map(
            lambda a: a.reshape((G, inner) + a.shape[1:]), params["blocks"])

        def layer_body(x, lp):
            lp = cast_block(constrain_tree(lp, block_axes))
            x, _, aux = _block_apply(cfg, lp, x, cos, sin, "train")
            x = constrain(x, ("act_batch", "act_seq", "act_embed"))
            return x, aux
        layer_body = jax.checkpoint(layer_body, policy=remat_policy,
                                    prevent_cse=False)

        def group_body(x, gp):
            x, auxs = jax.lax.scan(layer_body, x, gp)
            return x, auxs.sum()
        group_body = jax.checkpoint(group_body, policy=None, prevent_cse=False)

        x, auxs = jax.lax.scan(group_body, x, grouped)
        return x, None, auxs.sum()

    if cfg.remat and mode == "train":
        # remat only matters under differentiation; in prefill/decode it
        # makes partial-eval carry an f32 copy of the KV-cache stack.
        body = jax.checkpoint(body, policy=remat_policy, prevent_cse=False)

    xs = (params["blocks"], caches) if mode == "decode" else params["blocks"]
    x, (new_caches, auxs) = jax.lax.scan(body, x, xs)
    if mode == "train":
        new_caches = None
    return x, new_caches, auxs.sum()


def _mrope_positions(cfg, s_img: int, s_text: int):
    """Synthetic M-RoPE ids: image tokens on a (t=0, h, w) grid, text tokens
    sequential on all three channels after the spatial extent."""
    g = max(int(math.ceil(math.sqrt(max(s_img, 1)))), 1)
    i = jnp.arange(s_img)
    img = jnp.stack([jnp.zeros_like(i), i // g, i % g], axis=-1)
    t = jnp.arange(s_text) + g
    txt = jnp.stack([t, t, t], axis=-1)
    return jnp.concatenate([img, txt], axis=0)          # (S, 3)


def _cos_sin(cfg, positions, batch: int):
    Dh = cfg.resolved_head_dim
    if cfg.family == "vlm":
        pos3 = jnp.broadcast_to(positions[None], (batch,) + positions.shape)
        return mrope_cos_sin(pos3, Dh, cfg.rope_theta, cfg.mrope_sections)
    pos = jnp.broadcast_to(positions[None], (batch,) + positions.shape)
    return rope_cos_sin(pos, Dh, cfg.rope_theta)


def _embed_inputs(cfg, params, batch_dict):
    dt = jnp.dtype(cfg.dtype)
    tokens = batch_dict["tokens"]
    x = params["embed"][tokens].astype(dt)
    s_img = 0
    if cfg.family == "vlm" and "patch_embeds" in batch_dict:
        pp = params["patch_proj"]
        pe = batch_dict["patch_embeds"].astype(dt)
        img = jax.nn.gelu(pe @ pp["w1"].astype(dt)) @ pp["w2"].astype(dt)
        x = jnp.concatenate([img, x], axis=1)
        s_img = pe.shape[1]
    x = constrain(x, ("act_batch", "act_seq", "act_embed"))
    return x, s_img


def _logits(cfg, params, x):
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return constrain(x @ head.astype(x.dtype),
                     ("act_batch", "act_seq", "act_vocab"))


def lm_loss(cfg, params, batch_dict):
    x, s_img = _embed_inputs(cfg, params, batch_dict)
    B, S = x.shape[:2]
    if cfg.family == "vlm":
        positions = _mrope_positions(cfg, s_img, batch_dict["tokens"].shape[1])
    else:
        positions = jnp.arange(S)
    cos, sin = _cos_sin(cfg, positions, B)
    x, _, aux = _run_blocks(cfg, params, x, cos, sin, "train")
    logits = _logits(cfg, params, x)
    if cfg.family == "vlm":
        logits = logits[:, s_img:]                       # loss on text only
    loss = cross_entropy_loss(logits, batch_dict["labels"])
    if cfg.n_experts:
        loss = loss + 0.01 * aux
    return loss, {"aux_loss": aux}


def lm_prefill(cfg, params, batch_dict):
    x, s_img = _embed_inputs(cfg, params, batch_dict)
    B, S = x.shape[:2]
    if cfg.family == "vlm":
        positions = _mrope_positions(cfg, s_img, batch_dict["tokens"].shape[1])
    else:
        positions = jnp.arange(S)
    cos, sin = _cos_sin(cfg, positions, B)
    x, caches, _ = _run_blocks(cfg, params, x, cos, sin, "prefill")
    return _logits(cfg, params, x[:, -1:]), caches


def lm_decode(cfg, params, batch_dict, caches):
    """batch_dict: {"tokens": (B,1), "pos": scalar i32}. The KV caches have
    a fixed max length; ``pos`` is the write index."""
    dt = jnp.dtype(cfg.dtype)
    tokens = batch_dict["tokens"]
    pos = batch_dict["pos"]
    x = params["embed"][tokens].astype(dt)
    B = x.shape[0]
    if cfg.family == "vlm":
        # M-RoPE text position != cache position: text ids run sequentially
        # from the image grid extent, so rope_pos = pos + (grid - s_img),
        # carried as "mrope_delta" (qwen2-vl's rope-delta bookkeeping).
        rp = pos + batch_dict.get("mrope_delta", jnp.asarray(0, jnp.int32))
        p3 = jnp.stack([rp, rp, rp])[None, None, :]
        cos, sin = mrope_cos_sin(jnp.broadcast_to(p3, (B, 1, 3)),
                                 cfg.resolved_head_dim, cfg.rope_theta,
                                 cfg.mrope_sections)
    else:
        posv = jnp.broadcast_to(pos[None, None], (B, 1))
        cos, sin = rope_cos_sin(posv, cfg.resolved_head_dim, cfg.rope_theta)
    x, new_caches, _ = _run_blocks(cfg, params, x, cos, sin, "decode",
                                   caches=caches, pos=pos)
    return _logits(cfg, params, x), new_caches


def lm_cache_spec(cfg, batch: int, max_len: int):
    """(shape/dtype pytree, logical-axes pytree) for the stacked KV caches."""
    shape = (cfg.n_layers,) + kv_cache_shape(cfg, batch, max_len)
    dt = jnp.dtype(cfg.dtype)
    sds = jax.ShapeDtypeStruct(shape, dt)
    axes = ("layers", BATCH, "cache_seq", KV_HEADS, HEAD_DIM)
    return (sds, sds), (axes, axes)
