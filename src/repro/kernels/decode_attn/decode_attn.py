"""Pallas TPU flash-decode kernel: GQA attention of a γ+1-token verification
window (or a single decode token) over a long KV cache.

TPU adaptation of flash-decoding: the KV cache streams HBM→VMEM in
(S_TILE, hd) tiles with an online-softmax accumulator held in VMEM scratch
across the (sequential) cache-tile grid dimension. Per grid cell
(batch, kv_head) the query block is (T, G, hd) — all G query heads of one
KV group attend together, so the k-tile is loaded once per group rather than
once per query head (the GQA bandwidth win; this op is memory-bound with
arithmetic intensity ≈ T·G, far below the TPU ridge point).

``pos_map`` masking makes the same kernel serve append caches, ring-buffer
sliding-window caches (`long_500k`), and speculative-rollback stale-entry
exclusion — mask logic identical to models/attention.py.

Block shapes: S_TILE=512 lanes-aligned; hd ∈ {64, 128} both lane-aligned.
MXU use: the (T·G, hd) × (hd, S_TILE) score matmul and the (T·G, S_TILE) ×
(S_TILE, hd) value matmul.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from typing import Optional

from .. import resolve_interpret

S_TILE = 512
NEG_INF = -1e30


def _decode_attn_kernel(qpos_ref, q_ref, k_ref, v_ref, pm_ref, o_ref,
                        m_scr, l_scr, acc_scr, *, window: int, scale: float):
    """Grid (B, Hkv, S/S_TILE) — last dim sequential (online softmax).

    q: (1, T, 1, G, hd) | k,v: (1, S_TILE, 1, hd) | pm: (1, S_TILE)
    qpos: (1, T) | out: (1, T, 1, G, hd)
    scratch: m,l (T, G) f32; acc (T, G, hd) f32.
    """
    st = pl.program_id(2)

    @pl.when(st == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, :, 0, :, :].astype(jnp.float32)        # (T, G, hd)
    k = k_ref[0, :, 0, :].astype(jnp.float32)           # (ST, hd)
    v = v_ref[0, :, 0, :].astype(jnp.float32)           # (ST, hd)
    pm = pm_ref[0, :]                                   # (ST,)
    qpos = qpos_ref[0, :]                               # (T,)

    T, G, hd = q.shape
    scores = jax.lax.dot_general(
        q.reshape(T * G, hd), k,
        (((1,), (1,)), ((), ()))).reshape(T, G, -1) * scale   # (T, G, ST)

    valid = (pm[None, :] >= 0) & (pm[None, :] <= qpos[:, None])   # (T, ST)
    if window > 0:
        valid = valid & (pm[None, :] > qpos[:, None] - window)
    scores = jnp.where(valid[:, None, :], scores, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, scores.max(axis=-1))    # (T, G)
    alpha = jnp.exp(m_prev - m_new)
    e = jnp.exp(scores - m_new[..., None])              # (T, G, ST)
    e = jnp.where(valid[:, None, :], e, 0.0)
    l_scr[...] = l_scr[...] * alpha + e.sum(axis=-1)
    pv = jax.lax.dot_general(
        e.reshape(T * G, -1), v,
        (((1,), (0,)), ((), ()))).reshape(T, G, hd)
    acc_scr[...] = acc_scr[...] * alpha[..., None] + pv
    m_scr[...] = m_new

    @pl.when(st == pl.num_programs(2) - 1)
    def _done():
        l = l_scr[...]
        out = jnp.where(l[..., None] > 0, acc_scr[...] / jnp.maximum(
            l[..., None], 1e-20), 0.0)
        o_ref[0, :, 0, :, :] = out.astype(o_ref.dtype)


def decode_attn_call(q: jax.Array,        # (B, T, Hkv, G, hd)
                     k: jax.Array,        # (B, S, Hkv, hd)
                     v: jax.Array,
                     pos_map: jax.Array,  # (B, S)
                     q_pos: jax.Array,    # (B, T)
                     window: int = 0,
                     s_tile: int = S_TILE,
                     interpret: Optional[bool] = None) -> jax.Array:
    interpret = resolve_interpret(interpret)  # None → compiled on TPU only
    B, T, Hkv, G, hd = q.shape
    S = k.shape[1]
    s_tile = min(s_tile, S)
    assert S % s_tile == 0, "ops.py pads the cache to the tile size"
    grid = (B, Hkv, S // s_tile)
    kern = functools.partial(_decode_attn_kernel, window=window,
                             scale=1.0 / math.sqrt(hd))
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, T), lambda b, h, s: (b, 0)),
            pl.BlockSpec((1, T, 1, G, hd), lambda b, h, s: (b, 0, h, 0, 0)),
            pl.BlockSpec((1, s_tile, 1, hd), lambda b, h, s: (b, s, h, 0)),
            pl.BlockSpec((1, s_tile, 1, hd), lambda b, h, s: (b, s, h, 0)),
            pl.BlockSpec((1, s_tile), lambda b, h, s: (b, s)),
        ],
        out_specs=pl.BlockSpec((1, T, 1, G, hd),
                               lambda b, h, s: (b, 0, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, T, Hkv, G, hd), q.dtype),
        scratch_shapes=[pltpu.VMEM((T, G), jnp.float32),
                        pltpu.VMEM((T, G), jnp.float32),
                        pltpu.VMEM((T, G, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q_pos, q, k, v, pos_map)
