"""Public wrapper for flash attention: padding; the platform decides
whether the kernel runs interpreted (``core.platform.pallas_interpret``)."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ...core.platform import pallas_interpret
from .kernel import flash_attention_kernel


def _pad_axis(a, size: int, axis: int):
    pad = size - a.shape[axis]
    if pad <= 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths)


def flash_attention(q, k, v, *, causal: bool = True,
                    sm_scale: float | None = None,
                    block_q: int = 128, block_k: int = 128):
    """q: (B, Hq, Sq, D); k/v: (B, Hkv, Skv, D). Returns (B, Hq, Sq, D).

    Pads Sq/Skv up to tile multiples and D up to a lane multiple; padded KV
    columns are masked out by the causal/key-validity mask."""
    interpret = pallas_interpret()
    B, Hq, Sq, D = q.shape
    Skv = k.shape[2]
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(D))
    bq = min(block_q, int(np.ceil(Sq / 8) * 8))
    bk = min(block_k, int(np.ceil(Skv / 8) * 8))
    Sqp = int(np.ceil(Sq / bq) * bq)
    Skvp = int(np.ceil(Skv / bk) * bk)
    Dp = max(int(np.ceil(D / 128) * 128), 128) if not interpret else D

    qp = _pad_axis(_pad_axis(q, Sqp, 2), Dp, 3)
    kp = _pad_axis(_pad_axis(k, Skvp, 2), Dp, 3)
    vp = _pad_axis(_pad_axis(v, Skvp, 2), Dp, 3)
    out = flash_attention_kernel(qp, kp, vp, causal=causal,
                                 sm_scale=sm_scale, block_q=bq, block_k=bk,
                                 kv_len=Skv, kv_offset=Skv - Sq,
                                 interpret=interpret)
    return out[:, :, :Sq, :D]
