"""Pallas TPU paged flash-decode kernel: the block-table gather fused into
the decode grid.

Same online-softmax flash-decode as decode_attn.py, but K/V live in a
shared paged pool (n_blocks, block_size, Hkv, hd) and each sequence reads
only its own mapped blocks: the grid's sequential dimension walks the
sequence's LOGICAL block list ``0..n_log-1`` and a
``PrefetchScalarGridSpec`` scalar-prefetched block table indirects the K/V
BlockSpec index maps to the physical block — ``(tbl[b, i], 0, 0, 0)`` —
so paging costs zero extra HBM traffic on the hot path (no dense gather
materializes; each pool block streams HBM→VMEM exactly once per slot).

Block shapes follow the TPU tiling rule (the last two dims of every block
are (8, 128)-divisible or whole): one grid step loads the page of ALL
``Hkv`` heads, ``(1, bs, Hkv, hd)``, and loops over the heads in-kernel;
the window's query positions arrive repeated per query head as a
``(B, T·G, 1)`` column and the page's pos_map as a ``(NB, 1, bs)`` row, so
the validity mask is one ``(T·G, bs)`` compare with no in-kernel relayout.

Unmapped table entries (−1) clamp to block 0 for the prefetch and are
masked out wholesale in-kernel (``phys < 0``), exactly like a dense empty
slot; ``pos_map`` masking (speculative-rollback stale entries, sliding
window) carries over unchanged. int8 pools dequantize through the scores
and probabilities (``q·(k·s) = (q·k)·s``) from per-entry scales streamed
alongside the blocks as ``(NB, Hkv, bs)`` rows.

The dense kernel (decode_attn.py) + the XLA gather path
(models/kvcache.gather_layer_paged) stay as the reference oracles.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import resolve_interpret
from .decode_attn import NEG_INF


def _paged_decode_kernel(tbl_ref, qpos_ref, q_ref, k_ref, v_ref, pm_ref,
                         *rest, window: int, scale: float, length: int,
                         bs: int, quant: bool):
    """Grid (B, n_log) — last dim sequential over the slot's logical block
    list (online softmax); every step covers all Hkv heads.

    tbl (scalar prefetch): (B, n_log) | qpos: (1, T·G, 1) |
    q: (1, T, Hkv, G, hd) | k,v: (1, bs, Hkv, hd) — the PHYSICAL block
    tbl[b, i] | pm: (1, 1, bs) | [quant: ks,vs (1, Hkv, bs)] |
    out: (1, T, Hkv, G, hd) | scratch: m,l (Hkv, T·G, 1) f32;
    acc (Hkv, T·G, hd) f32.
    """
    if quant:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    b = pl.program_id(0)
    i = pl.program_id(1)
    _, T, Hkv, G, hd = q_ref.shape

    @pl.when(i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    phys = tbl_ref[b, i]                                # −1 = unmapped
    pm = pm_ref[0]                                      # (1, bs)
    qpos = qpos_ref[0]                                  # (T·G, 1)
    # logical positions this block covers; past-length tail of the last
    # block is padding
    j = i * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
    valid = (phys >= 0) & (j < length) & (pm >= 0) & (pm <= qpos)  # (TG, bs)
    if window > 0:
        valid = valid & (pm > qpos - window)

    for h in range(Hkv):
        q = q_ref[0, :, h, :, :].astype(jnp.float32).reshape(T * G, hd)
        k = k_ref[0, :, h, :].astype(jnp.float32)       # (bs, hd)
        v = v_ref[0, :, h, :].astype(jnp.float32)
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ()))) * scale     # (T·G, bs)
        if quant:
            scores = scores * ks_ref[0, h:h + 1, :]
        scores = jnp.where(valid, scores, NEG_INF)

        m_prev = m_scr[h]                               # (T·G, 1)
        m_new = jnp.maximum(m_prev, scores.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        e = jnp.where(valid, jnp.exp(scores - m_new), 0.0)
        l_scr[h] = l_scr[h] * alpha + e.sum(axis=-1, keepdims=True)
        if quant:
            e = e * vs_ref[0, h:h + 1, :]
        pv = jax.lax.dot_general(e, v, (((1,), (0,)), ((), ())))
        acc_scr[h] = acc_scr[h] * alpha + pv
        m_scr[h] = m_new

    @pl.when(i == pl.num_programs(1) - 1)
    def _done():
        for h in range(Hkv):
            l = l_scr[h]
            out = jnp.where(l > 0, acc_scr[h] / jnp.maximum(l, 1e-20), 0.0)
            o_ref[0, :, h, :, :] = out.reshape(T, G, hd).astype(o_ref.dtype)


def paged_decode_attention(q: jax.Array,            # (B, T, Hkv, G, hd)
                           k_pool: jax.Array,       # (NB, bs, Hkv, hd)
                           v_pool: jax.Array,
                           k_scale: Optional[jax.Array],  # (NB, bs, Hkv)
                           v_scale: Optional[jax.Array],
                           pos_map: jax.Array,      # (NB, bs)
                           block_table: jax.Array,  # (B, n_log) int32
                           q_pos: jax.Array,        # (B, T)
                           length: int,
                           window: int = 0,
                           interpret: Optional[bool] = None) -> jax.Array:
    """Fused paged GQA flash-decode over ONE layer's pool view. Returns the
    attention context (B, T, Hkv, G, hd) in ``q.dtype`` (the wo projection
    stays outside, in models/attention.py)."""
    interpret = resolve_interpret(interpret)
    B, T, Hkv, G, hd = q.shape
    NB, bs = pos_map.shape
    n_log = block_table.shape[1]
    quant = k_scale is not None

    # query position per (t, g) row of the flattened score tile, as a column
    qpos_rows = jnp.repeat(q_pos.astype(jnp.int32), G, axis=1)[..., None]
    pm_rows = pos_map.reshape(NB, 1, bs)

    # unmapped (−1) prefetches clamp to block 0; the kernel masks it out
    def page(b, i, tbl):
        return (jnp.maximum(tbl[b, i], 0), 0, 0, 0)

    def page_row(b, i, tbl):
        return (jnp.maximum(tbl[b, i], 0), 0, 0)

    in_specs = [
        pl.BlockSpec((1, T * G, 1), lambda b, i, tbl: (b, 0, 0)),
        pl.BlockSpec((1, T, Hkv, G, hd), lambda b, i, tbl: (b, 0, 0, 0, 0)),
        pl.BlockSpec((1, bs, Hkv, hd), page),
        pl.BlockSpec((1, bs, Hkv, hd), page),
        pl.BlockSpec((1, 1, bs), page_row),
    ]
    inputs = [qpos_rows, q, k_pool, v_pool, pm_rows]
    if quant:
        in_specs += [pl.BlockSpec((1, Hkv, bs), page_row)] * 2
        inputs += [jnp.swapaxes(k_scale, 1, 2), jnp.swapaxes(v_scale, 1, 2)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, n_log),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, T, Hkv, G, hd),
                               lambda b, i, tbl: (b, 0, 0, 0, 0)),
        scratch_shapes=[pltpu.VMEM((Hkv, T * G, 1), jnp.float32),
                        pltpu.VMEM((Hkv, T * G, 1), jnp.float32),
                        pltpu.VMEM((Hkv, T * G, hd), jnp.float32)],
    )
    kern = functools.partial(_paged_decode_kernel, window=window,
                             scale=1.0 / math.sqrt(hd), length=length,
                             bs=bs, quant=quant)
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, T, Hkv, G, hd), q.dtype),
        interpret=interpret,
    )(block_table, *inputs)
