"""Pallas TPU kernel for the Mamba2/SSD chunked scan.

State-space duality on the MXU: within a chunk of L tokens the recurrence is
computed as a masked (L, L) quadratic form (three MXU matmuls per chunk —
C·Bᵀ scores, scores·x, and the state in/out products); across chunks the
(hd, N) state carries in VMEM scratch along the sequential chunk grid
dimension. This is the TPU-native shape of the SSD algorithm: the GPU
implementation leans on warp-level scans, which have no MXU analogue —
the chunked duality *is* the adaptation (DESIGN.md §3).

Grid (B, nh, S/L): batch and head parallel, chunks sequential. Block sizes:
L=128 tokens (8×128-aligned score tiles), hd=64/128 lanes, N=64/128 lanes.
VMEM per cell ≈ L·(hd+2N)·4 + L²·4 + hd·N·4 ≈ 170 KiB at L=128, hd=64,
N=128 — comfortably within the 16 MiB v5e VMEM budget with double-buffering.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 128


def _ssd_kernel(x_ref, b_ref, c_ref, dt_ref, a_ref, h0_ref,
                y_ref, hout_ref, h_scr):
    """x: (1,L,1,hd) | B,C: (1,L,N) | dt: (1,L,1) | A: (1,) | h0: (1,1,hd,N)
    outputs: y (1,L,1,hd); h_out (1,1,hd,N) at the last chunk."""
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        h_scr[...] = h0_ref[0, 0].astype(jnp.float32)

    x = x_ref[0, :, 0, :].astype(jnp.float32)       # (L, hd)
    Bm = b_ref[0].astype(jnp.float32)               # (L, N)
    Cm = c_ref[0].astype(jnp.float32)               # (L, N)
    dt = dt_ref[0, :, 0].astype(jnp.float32)        # (L,)
    A = a_ref[0].astype(jnp.float32)                # scalar

    L = x.shape[0]
    la = A * dt                                     # (L,) log-decay ≤ 0
    Lc = jnp.cumsum(la)

    h = h_scr[...]                                  # (hd, N)
    # inter-chunk: y_state[t] = exp(Lc_t) · C_t h^T
    y_state = jax.lax.dot_general(Cm, h, (((1,), (1,)), ((), ()))) \
        * jnp.exp(Lc)[:, None]                      # (L, hd)

    # intra-chunk masked quadratic form
    seg = Lc[:, None] - Lc[None, :]                 # (L, L)
    mask = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    w = jnp.where(mask, jnp.exp(seg), 0.0)
    cb = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())))   # (L, L)
    scores = cb * w * dt[None, :]
    y_intra = jnp.dot(scores, x)                    # (L, hd)
    y_ref[0, :, 0, :] = (y_state + y_intra).astype(y_ref.dtype)

    # state update: h' = exp(Lc_last)·h + Σ_s exp(Lc_last − Lc_s)·dt_s·x_s⊗B_s
    decay_out = jnp.exp(Lc[-1] - Lc) * dt           # (L,)
    contrib = jax.lax.dot_general(x * decay_out[:, None], Bm,
                                  (((0,), (0,)), ((), ())))      # (hd, N)
    h_scr[...] = jnp.exp(Lc[-1]) * h + contrib

    @pl.when(c == pl.num_programs(2) - 1)
    def _done():
        hout_ref[0, 0] = h_scr[...].astype(hout_ref.dtype)


def ssd_call(x: jax.Array,    # (B, S, nh, hd)
             Bm: jax.Array,   # (B, S, N)
             Cm: jax.Array,   # (B, S, N)
             dt: jax.Array,   # (B, S, nh)
             A: jax.Array,    # (nh,)
             h_in: jax.Array, # (B, nh, hd, N) f32
             chunk: int = CHUNK,
             interpret=None):
    from .. import resolve_interpret
    interpret = resolve_interpret(interpret)  # None → compiled on TPU only
    B, S, nh, hd = x.shape
    N = Bm.shape[-1]
    assert S % chunk == 0, "ops.py pads the sequence to the chunk size"
    grid = (B, nh, S // chunk)
    y, h_out = pl.pallas_call(
        _ssd_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, 1, hd), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, 1), lambda b, h, c: (b, c, h)),
            pl.BlockSpec((1,), lambda b, h, c: (h,)),
            pl.BlockSpec((1, 1, hd, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, 1, hd), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, 1, hd, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, nh, hd), jnp.float32),
            jax.ShapeDtypeStruct((B, nh, hd, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, Bm, Cm, dt, A, h_in)
    return y, h_out
