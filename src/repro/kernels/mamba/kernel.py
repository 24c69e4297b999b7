"""Chunked SSD scan (Pallas TPU kernel) — the Mamba2 training hot spot.

The selective-state-space recurrence is sequential in time; the SSD
formulation (Dao & Gu, 2024) converts it into chunk-local MATMULS plus a
tiny cross-chunk state carry — exactly the TPU-friendly restructuring
DESIGN.md §2 calls for (MXU matmuls inside a chunk, one (N, P) state in VMEM
scratch across chunks):

  within chunk c of length L (log-decays alog, cumsum cs):
    L_mat[s,t] = exp(cs[s] - cs[t]) * (s >= t)          intra-chunk decay
    y_intra    = ((C B^T) * L_mat) @ x                  (L,N)x(N,L) + (L,L)x(L,P)
    y_inter[s] = exp(cs[s]) * C[s] @ h_carry            (L,N)x(N,P)
    h_carry    = exp(cs[L-1]) h_carry + B^T @ (x * exp(cs[L-1]-cs))

Grid: (B*H, chunks) with chunks innermost; h_carry persists in VMEM scratch
across the chunk axis. B/C are shared across heads (single state group) —
their BlockSpec index maps divide the flattened batch*head index, so nothing
is materialized per head.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, alog_ref, b_ref, c_ref, y_ref, hout_ref, h_scr, *,
                nchunks: int):
    c_idx = pl.program_id(1)

    @pl.when(c_idx == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    x = x_ref[0, 0].astype(jnp.float32)          # (L, P)
    al = alog_ref[0, 0].astype(jnp.float32)      # (L,)
    B = b_ref[0].astype(jnp.float32)             # (L, N)
    C = c_ref[0].astype(jnp.float32)             # (L, N)
    L = x.shape[0]

    cs = jnp.cumsum(al)                          # (L,)
    # intra-chunk
    diff = cs[:, None] - cs[None, :]
    tri = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    L_mat = jnp.where(tri, jnp.exp(diff), 0.0)
    G = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (L, L)
    y = jax.lax.dot_general(G * L_mat, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (L, P)
    # inter-chunk (carry-in state)
    h = h_scr[...]                               # (N, P)
    y = y + jnp.exp(cs)[:, None] * jax.lax.dot_general(
        C, h, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    # state carry-out
    decay_to_end = jnp.exp(cs[-1] - cs)          # (L,)
    h_scr[...] = jnp.exp(cs[-1]) * h + jax.lax.dot_general(
        B, x * decay_to_end[:, None], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)      # (N, P)

    y_ref[0, 0] = y.astype(y_ref.dtype)

    @pl.when(c_idx == nchunks - 1)
    def _emit_state():
        hout_ref[0, 0] = h_scr[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan_kernel(x, alog, B, C, *, chunk: int = 128, interpret: bool):
    """x: (Bsz, H, S, P); alog: (Bsz, H, S); B/C: (Bsz, S, N). S % chunk == 0
    (ops.py pads). Returns (y (Bsz, H, S, P), h_final (Bsz, H, N, P))."""
    Bsz, H, S, P = x.shape
    N = B.shape[-1]
    nchunks = S // chunk
    grid = (Bsz * H, nchunks)
    y, h = pl.pallas_call(
        functools.partial(_ssd_kernel, nchunks=nchunks),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda bh, c, H=H: (bh // H, bh % H, c, 0)),
            pl.BlockSpec((1, 1, chunk), lambda bh, c, H=H: (bh // H, bh % H, c)),
            pl.BlockSpec((1, chunk, N), lambda bh, c, H=H: (bh // H, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda bh, c, H=H: (bh // H, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda bh, c, H=H: (bh // H, bh % H, c, 0)),
            pl.BlockSpec((1, 1, N, P), lambda bh, c, H=H: (bh // H, bh % H, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bsz, H, S, P), x.dtype),
            jax.ShapeDtypeStruct((Bsz, H, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        interpret=interpret,
    )(x, alog, B, C)
    return y, h
