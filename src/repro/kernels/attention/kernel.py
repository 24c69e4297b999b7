"""Flash attention forward (Pallas TPU kernel).

Streaming-softmax tiling: grid (B, Hq, Q-tiles, KV-tiles) with the KV axis
innermost; running max / normalizer / accumulator live in VMEM scratch and
persist across KV steps (TPU grid execution is sequential). GQA is handled
in the K/V BlockSpec index maps (kv_head = q_head // group) — no KV head
materialization. Q/K/V tiles are (bq, D)/(bk, D) VMEM blocks; D padded to
128 by ops.py so the (bq, bk) logits contraction is MXU-aligned.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  sm_scale: float, causal: bool, bq: int, bk: int,
                  nkv: int, kv_offset: int, kv_len: int):
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32) * sm_scale        # (bq, D)
    k = k_ref[0, 0].astype(jnp.float32)                   # (bk, D)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (bq, bk)
    ki = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    s = jnp.where(ki < kv_len, s, NEG_INF)                # padded-key validity
    if causal:
        i = pl.program_id(2)
        qi = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + kv_offset
        s = jnp.where(qi >= ki, s, NEG_INF)

    m_prev = m_scr[...]                                   # (bq, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)                                # fully-masked rows: exp(NEG_INF*0)=e^0 guarded below
    p = jnp.where(s <= NEG_INF, 0.0, p)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = alpha * l_scr[...] + p.sum(axis=1, keepdims=True)
    acc_scr[...] = alpha * acc_scr[...] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(j == nkv - 1)
    def _finish():
        l = l_scr[...]
        o_ref[0, 0] = (acc_scr[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "sm_scale", "block_q", "block_k", "interpret", "kv_offset",
    "kv_len"))
def flash_attention_kernel(q, k, v, *, causal: bool, sm_scale: float,
                           block_q: int, block_k: int, kv_len: int,
                           kv_offset: int = 0, interpret: bool):
    """q: (B, Hq, Sq, D); k/v: (B, Hkv, Skv, D). Sq % block_q == 0,
    Skv % block_k == 0 (ops.py pads; keys at index >= kv_len are masked).
    kv_offset is the causal position of q row 0 (computed by ops.py from the
    UNPADDED lengths: kv_len_actual - q_len_actual)."""
    B, Hq, Sq, D = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    group = Hq // Hkv
    nq, nkv = Sq // block_q, Skv // block_k
    grid = (B, Hq, nq, nkv)
    return pl.pallas_call(
        functools.partial(_flash_kernel, sm_scale=sm_scale, causal=causal,
                          bq=block_q, bk=block_k, nkv=nkv, kv_len=kv_len,
                          kv_offset=kv_offset),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, i, j, g=group: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, i, j, g=group: (b, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
