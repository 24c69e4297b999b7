"""Public wrapper for the chunked SSD scan; the platform decides whether the
kernel runs interpreted (``core.platform.pallas_interpret``)."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ...core.platform import pallas_interpret
from .kernel import ssd_scan_kernel


def ssd_scan(x, alog, B, C, *, chunk: int = 128):
    """x: (Bsz, S, H, P); alog: (Bsz, S, H); B/C: (Bsz, S, N).
    Returns (y (Bsz, S, H, P), h_final (Bsz, H, N, P)).

    Pads S up to a chunk multiple with zero inputs and zero log-decay —
    appended steps multiply the state by exp(0)=1 and add nothing, so
    trailing padding is exact (padded outputs are sliced off)."""
    Bsz, S, H, P = x.shape
    chunk = min(chunk, int(np.ceil(S / 8) * 8))
    Sp = int(np.ceil(S / chunk) * chunk)
    if Sp != S:
        pad = [(0, 0), (0, Sp - S)]
        x = jnp.pad(x, pad + [(0, 0), (0, 0)])
        alog = jnp.pad(alog, pad + [(0, 0)])
        B = jnp.pad(B, pad + [(0, 0)])
        C = jnp.pad(C, pad + [(0, 0)])
    xt = jnp.moveaxis(x, 2, 1)           # (Bsz, H, S, P)
    at = jnp.moveaxis(alog, 2, 1)        # (Bsz, H, S)
    y, h = ssd_scan_kernel(xt, at, B, C, chunk=chunk,
                           interpret=pallas_interpret())
    y = jnp.moveaxis(y, 1, 2)[:, :S]
    return y, h
