"""Grouped-query attention with the variants the assigned archs need:
qk_norm (qwen3), qkv bias (qwen2.5), sliding window (long-context serving),
cross-attention (whisper decoder), ring-buffer KV caches, and pos_map-masked
decode (speculative rollback; see models/kvcache.py).

Functions are per-layer and pure; model.py stacks their params and scans.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from .layers import apply_rope, causal_mask, dense_init, rms_norm
from .kvcache import (gather_layer_paged, paged_update_layer,
                      update_layer_cache)
from ..sharding.runtime import constrain_qkv


def init_attn_params(key: jax.Array, cfg: ModelConfig, dtype,
                     cross: bool = False) -> dict:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, h, hd), dtype, fan_in=d),
        "wk": dense_init(ks[1], (d, kv, hd), dtype, fan_in=d),
        "wv": dense_init(ks[2], (d, kv, hd), dtype, fan_in=d),
        "wo": dense_init(ks[3], (h, hd, d), dtype, fan_in=h * hd),
    }
    if cfg.qkv_bias and not cross:
        p["bq"] = jnp.zeros((h, hd), dtype)
        p["bk"] = jnp.zeros((kv, hd), dtype)
        p["bv"] = jnp.zeros((kv, hd), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), dtype)
        p["k_norm"] = jnp.zeros((hd,), dtype)
    return p


def _project_q(x, p, cfg):
    q = jnp.einsum("btd,dhk->bthk", x, p["wq"])
    if "bq" in p:
        q = q + p["bq"]
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
    return constrain_qkv(q)


def _project_kv(x, p, cfg):
    k = jnp.einsum("btd,dhk->bthk", x, p["wk"])
    v = jnp.einsum("btd,dhk->bthk", x, p["wv"])
    if "bk" in p:
        k, v = k + p["bk"], v + p["bv"]
    if cfg.qk_norm:
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    return constrain_qkv(k), constrain_qkv(v)


def _repeat_kv(kv, H):
    """(B,S,Hkv,hd) → (B,S,H,hd). Repeating KV to full heads keeps attention
    a clean 4-D einsum that GSPMD shards exactly on the head dim (H is a
    multiple of the model axis for most archs) — the 5-D (Hkv,G)-split
    formulation forced involuntary replication of O(S²) score tensors in
    the backward pass (§Perf cycle 4). The GQA bandwidth saving is a
    property of the serving kernel (kernels/decode_attn), not of the
    training einsum — same trade Megatron/MaxText make."""
    Hkv = kv.shape[2]
    if Hkv == H:
        return kv
    return jnp.repeat(kv, H // Hkv, axis=2)


def _gqa_scores(q, k):
    """q: (B,T,H,hd), k: (B,S,Hkv,hd) → (B,H,T,S)."""
    k = _repeat_kv(k, q.shape[2])
    return jnp.einsum("bthd,bshd->bhts", q, k) / math.sqrt(q.shape[-1])


def _gqa_out(weights, v, p):
    """weights: (B,H,T,S), v: (B,S,Hkv,hd) → (B,T,D)."""
    v = _repeat_kv(v, weights.shape[1])
    ctx = jnp.einsum("bhts,bshd->bthd", weights, v)
    return jnp.einsum("bthk,hkd->btd", ctx, p["wo"])


def attention_train(x: jax.Array, p: dict, cfg: ModelConfig,
                    positions: Optional[jax.Array] = None,
                    window: Optional[int] = None,
                    prefix_len: int = 0, q_chunk: int = 1024) -> jax.Array:
    """Full-sequence causal self-attention (training / prefill compute path).

    ``prefix_len`` marks a bidirectional prefix (VLM image tokens attend
    freely within the prefix; text remains causal) — 0 for plain LMs.

    Long sequences (> q_chunk) process queries in chunks via ``lax.scan`` so
    the (T, T) score matrix never materializes — the flash-attention memory
    shape, required for the 32k prefill/train shapes (a 32k² f32 score
    tensor would be ~4 GB per head). Chunks attend to the full (masked) K,
    trading ≤2× causal-triangle flops for O(C·T) memory.
    """
    B, T, _ = x.shape
    if positions is None:
        positions = jnp.arange(T)[None, :].astype(jnp.int32)
    q = apply_rope(_project_q(x, p, cfg), positions, cfg.rope_theta)
    k, v = _project_kv(x, p, cfg)
    k = apply_rope(k, positions, cfg.rope_theta)
    w = window if window is not None else cfg.sliding_window

    def masked_attend(q_blk, offset):
        """q_blk: (B, C, H, hd); offset: absolute pos of q_blk[…,0]."""
        C = q_blk.shape[1]
        mask = causal_mask(C, T, offset, w)
        if prefix_len > 0:
            pre = ((jnp.arange(C)[:, None] + offset) < prefix_len) & \
                (jnp.arange(T)[None, :] < prefix_len)
            mask = mask | pre
        scores = _gqa_scores(q_blk, k)
        scores = jnp.where(mask[None, None],
                           scores.astype(jnp.float32), -jnp.inf)
        weights = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
        return _gqa_out(weights, v, p)

    if T <= q_chunk or T % q_chunk != 0:
        return masked_attend(q, 0)

    n_chunks = T // q_chunk
    q_blocks = q.reshape(B, n_chunks, q_chunk, *q.shape[2:]).swapaxes(0, 1)
    offsets = jnp.arange(n_chunks) * q_chunk

    def step(_, inp):
        qb, off = inp
        return None, masked_attend(qb, off)

    _, out = jax.lax.scan(step, None, (q_blocks, offsets))
    # masked_attend output is already projected: (n_chunks, B, C, d_model)
    return out.swapaxes(0, 1).reshape(B, T, x.shape[-1])


def attention_bidir(x: jax.Array, p: dict, cfg: ModelConfig) -> jax.Array:
    """Bidirectional self-attention (whisper encoder)."""
    B, T, _ = x.shape
    positions = jnp.arange(T)[None, :].astype(jnp.int32)
    q = apply_rope(_project_q(x, p, cfg), positions, cfg.rope_theta)
    k, v = _project_kv(x, p, cfg)
    k = apply_rope(k, positions, cfg.rope_theta)
    scores = _gqa_scores(q, k).astype(jnp.float32)
    weights = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    return _gqa_out(weights, v, p)


def attention_cross(x: jax.Array, p: dict, cfg: ModelConfig,
                    enc_k: jax.Array, enc_v: jax.Array) -> jax.Array:
    """Cross-attention over precomputed encoder K/V (whisper decoder)."""
    q = _project_q(x, p, cfg)   # no rope on cross-attn queries
    scores = _gqa_scores(q, enc_k).astype(jnp.float32)
    weights = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    return _gqa_out(weights, v=enc_v, p=p)


def cross_kv(p: dict, cfg: ModelConfig, enc_out: jax.Array):
    """Precompute encoder K/V once per request (the whisper 'prefill')."""
    return _project_kv(enc_out, p, cfg)


def attention_decode(x_new: jax.Array, p: dict, cfg: ModelConfig,
                     k_cache: jax.Array, v_cache: jax.Array,
                     pos_map: jax.Array, pos: jax.Array, ring: bool,
                     window: int = 0, uniform_pos: bool = False,
                     slot_off: Optional[jax.Array] = None,
                     pos_off: Optional[jax.Array] = None,
                     win_mask: Optional[jax.Array] = None,
                     layer: Optional[jax.Array] = None):
    """Decode/verify step: write the (B,T) window into the cache, attend over
    valid slots.

    x_new: (B, T, D); pos: (B,) absolute position of x_new[:, 0].
    Validity mask per slot s for query t:  0 ≤ pos_map[s] ≤ pos+t, and
    pos_map[s] > pos+t − window when sliding. Stale speculative entries
    (pos_map beyond the committed position) are excluded automatically.

    Tree speculation (``slot_off``/``pos_off``/``win_mask``): token t
    writes slot ``pos + slot_off[t]`` at logical position
    ``pos + pos_off[t]`` (RoPE phase and pos_map value), and for cache
    slots inside the window region ``[pos, pos + win_mask.shape[1])`` the
    validity of slot ``pos + j`` for query t is OVERRIDDEN by
    ``win_mask[t, j]`` — sibling branches tie on position, so the base
    ``slot_pos ≤ q_pos`` rule cannot separate them; the ancestor bitmap
    does. Slots outside the region keep the base rule (the committed
    prefix stays visible).

    ``layer`` (int32 scalar): the caches are the whole (L, B, S, ...)
    stack, written and attended at layer ``layer`` (see
    :func:`repro.models.kvcache.update_layer_cache`).
    Returns (out, k_cache, v_cache, pos_map).
    """
    B, T, _ = x_new.shape
    off = jnp.arange(T) if pos_off is None else pos_off
    abs_pos = pos[:, None] + off[None, :]                      # (B, T)
    q = apply_rope(_project_q(x_new, p, cfg), abs_pos, cfg.rope_theta)
    k_new, v_new = _project_kv(x_new, p, cfg)
    k_new = apply_rope(k_new, abs_pos, cfg.rope_theta)
    k_cache, v_cache, pos_map = update_layer_cache(
        k_cache, v_cache, pos_map, k_new, v_new, pos, ring,
        uniform_pos=uniform_pos, slot_off=slot_off, pos_off=pos_off,
        layer=layer)
    kc, vc, pm = (k_cache, v_cache, pos_map) if layer is None else (
        c[layer] for c in (k_cache, v_cache, pos_map))
    out = _attend_cached(q, kc, vc, pm, abs_pos, window, p["wo"],
                         x_new.dtype, win_mask=win_mask, pos=pos)
    return out, k_cache, v_cache, pos_map


def _attend_cached(q, k_cache, v_cache, pos_map, abs_pos, window, wo,
                   out_dtype, win_mask=None, pos=None):
    """Attend rope'd queries (B,T,H,hd) over a position-ordered cache view
    (B,S,Hkv,hd) + pos_map (B,S). Shared by the dense and paged decode
    paths — the paged path gathers its pool into exactly this view, so both
    run the identical einsum/mask/softmax program (bit-identical on equal
    values).

    decode is memory-bound and has no backward: use the GROUPED einsum so
    the KV cache is read once per kv-head, not G x via repeat (the 4-D
    repeat form serves the training path's GSPMD-friendly backward; the
    TPU serving kernel kernels/decode_attn implements the same grouping)."""
    B_, T_, H_, hd_ = q.shape
    Hkv_ = k_cache.shape[2]
    G_ = H_ // Hkv_
    qg = q.reshape(B_, T_, Hkv_, G_, hd_)
    # f32 accumulation via preferred_element_type: a separate .astype(f32)
    # made XLA materialize an f32 copy of the whole cache shard per layer
    # (§Perf decode cycle: 4 GiB x L buffers)
    scores = jnp.einsum("btkgh,bskh->bkgts", qg, k_cache,
                        preferred_element_type=jnp.float32) / math.sqrt(hd_)
    slot_pos = pos_map[:, None, None, None, :]                  # (B,1,1,1,S)
    q_pos = abs_pos[:, None, None, :, None]                     # (B,1,1,T,1)
    valid = (slot_pos >= 0) & (slot_pos <= q_pos)
    if window > 0:
        valid = valid & (slot_pos > q_pos - window)
    if win_mask is not None:
        # Tree window override: slot pos+j obeys win_mask[t, j] instead of
        # the position rule, for j in [0, Wn) (see attention_decode).
        Wn = win_mask.shape[1]
        S_ = pos_map.shape[1]
        rel = jnp.arange(S_)[None, :] - pos[:, None]            # (B, S)
        in_region = (rel >= 0) & (rel < Wn)
        ov = jnp.take(win_mask, jnp.clip(rel, 0, Wn - 1), axis=1)  # (T,B,S)
        ov = jnp.moveaxis(ov, 0, 1)[:, None, None, :, :]        # (B,1,1,T,S)
        valid = jnp.where(in_region[:, None, None, None, :],
                          ov, valid)
    scores = jnp.where(valid, scores, -jnp.inf)
    weights = jax.nn.softmax(scores, axis=-1).astype(out_dtype)
    ctx = jnp.einsum("bkgts,bskh->btkgh", weights, v_cache)
    ctx = ctx.reshape(B_, T_, H_, hd_)
    return jnp.einsum("bthk,hkd->btd", ctx, wo)


def attention_decode_paged(x_new: jax.Array, p: dict, cfg: ModelConfig,
                           k_pool: jax.Array, v_pool: jax.Array,
                           k_scale, v_scale, pos_map: jax.Array,
                           block_table: jax.Array, pos: jax.Array,
                           ring: bool, length: int, window: int = 0,
                           use_kernel: Optional[bool] = None):
    """Paged decode/verify step: write the (B,T) window into the block pool
    through the slot block tables, then attend over the slot's mapped
    blocks. Single-layer pool views: k/v (NB, bs, Hkv, hd), pos_map
    (NB, bs); block_table (B, n_log) is shared across layers and NOT
    updated here.

    Identical masking semantics to :func:`attention_decode` — the fp pool
    is bit-identical to a dense cache of size ``length`` (int8 pools are
    approximate by construction). ``use_kernel=None`` auto-selects the
    fused Pallas paged kernel on TPU backends and the XLA gather path
    elsewhere. Returns (out, k_pool, v_pool, k_scale, v_scale, pos_map).
    """
    B, T, _ = x_new.shape
    abs_pos = pos[:, None] + jnp.arange(T)[None, :]            # (B, T)
    q = apply_rope(_project_q(x_new, p, cfg), abs_pos, cfg.rope_theta)
    k_new, v_new = _project_kv(x_new, p, cfg)
    k_new = apply_rope(k_new, abs_pos, cfg.rope_theta)
    k_pool, v_pool, k_scale, v_scale, pos_map = paged_update_layer(
        k_pool, v_pool, k_scale, v_scale, pos_map, block_table,
        k_new, v_new, pos, ring, length)

    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    if use_kernel:
        # fused path: the kernel grid walks each slot's block list via
        # scalar-prefetch indirection — no dense gather materializes
        from ..kernels.decode_attn.paged import paged_decode_attention
        B_, T_, H_, hd_ = q.shape
        Hkv_ = k_pool.shape[2]
        qg = q.reshape(B_, T_, Hkv_, H_ // Hkv_, hd_)
        ctx = paged_decode_attention(qg, k_pool, v_pool, k_scale, v_scale,
                                     pos_map, block_table, abs_pos,
                                     length=length, window=window)
        ctx = ctx.astype(x_new.dtype).reshape(B_, T_, H_, hd_)
        out = jnp.einsum("bthk,hkd->btd", ctx, p["wo"])
    else:
        k_d, v_d, pm_d = gather_layer_paged(
            k_pool, v_pool, k_scale, v_scale, pos_map, block_table,
            length, x_new.dtype)
        out = _attend_cached(q, k_d, v_d, pm_d, abs_pos, window, p["wo"],
                             x_new.dtype)
    return out, k_pool, v_pool, k_scale, v_scale, pos_map
