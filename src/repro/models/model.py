"""Unified model zoo assembler.

One :class:`Model` class covers all six families via ``cfg.arch_type``:

- ``dense`` / ``vlm``  — GQA transformer LM (vlm consumes stub patch embeds
  as a bidirectional prefix),
- ``moe``              — GQA attention + GShard capacity-dispatch MoE FFN,
- ``ssm``              — Mamba2/SSD stack (attention-free),
- ``hybrid``           — Zamba2-style Mamba2 backbone + one *shared*
  attention block invoked every ``attn_every`` layers,
- ``encdec``           — whisper-style audio encoder (stub conv frontend
  embeddings) + text decoder with cross-attention.

API (uniform across families, everything jit/pjit-able):

    params = model.init_params(key)
    logits, aux = model.forward_train(params, batch)
    logits, cache = model.prefill(params, tokens, frontend=..., slots=N)
    logits, cache = model.decode_step(params, token, cache, pos)   # T = 1
    logits, cache = model.verify_step(params, window, cache, pos)  # T = γ+1

Layers are stacked and scanned (``lax.scan``) so HLO size and compile time
stay flat in depth — required for the 80-layer archs in the dry-run. In
the dense decode/verify loop the stacked KV cache is loop state, not a
scanned input: layer ``l`` writes its window into row ``l`` of the carried
stack and attends over that row, so XLA updates one buffer in place through
the layer loop and through any loop around the step (the draft's γ scan),
and a donated cache aliases the step's output.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..configs.base import ModelConfig
from .layers import dense_init, dtype_of, rms_norm, swiglu
from .attention import (attention_bidir, attention_cross, attention_decode,
                        attention_decode_paged, attention_train, cross_kv,
                        init_attn_params)
from .moe import init_moe_params, moe_block
from .ssm import SSDState, init_ssm_params, ssm_block_decode, ssm_block_train
from .kvcache import (AttnCache, PagedAttnCache, SSMCache, init_attn_cache,
                      init_paged_attn_cache, init_ssm_cache)
from ..sharding.runtime import (constrain, constrain_head_in,
                                constrain_logits)


class EncDecCache(NamedTuple):
    self_attn: AttnCache
    cross_k: jax.Array     # (L, B, F, Hkv, hd)
    cross_v: jax.Array


class HybridCacheT(NamedTuple):
    ssm: SSMCache
    shared_attn: AttnCache   # L axis = number of shared-block invocations


def _stack_init(key: jax.Array, n: int, fn):
    return jax.vmap(fn)(jax.random.split(key, n))


class Model:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.dtype = dtype_of(cfg.dtype)

    # ------------------------------------------------------------------ init

    def _init_block(self, key: jax.Array) -> dict:
        cfg, dt = self.cfg, self.dtype
        ks = jax.random.split(key, 3)
        if cfg.arch_type in ("dense", "vlm", "moe", "encdec"):
            p = {"ln1": jnp.zeros((cfg.d_model,), dt),
                 "ln2": jnp.zeros((cfg.d_model,), dt),
                 "attn": init_attn_params(ks[0], cfg, dt)}
            if cfg.arch_type == "moe":
                p["moe"] = init_moe_params(ks[1], cfg, dt)
            else:
                f = cfg.d_ff
                k1, k2, k3 = jax.random.split(ks[1], 3)
                p["mlp"] = {
                    "w_gate": dense_init(k1, (cfg.d_model, f), dt),
                    "w_up": dense_init(k2, (cfg.d_model, f), dt),
                    "w_down": dense_init(k3, (f, cfg.d_model), dt, fan_in=f)}
            if cfg.arch_type == "encdec":     # decoder gets cross-attention
                p["ln_x"] = jnp.zeros((cfg.d_model,), dt)
                p["xattn"] = init_attn_params(ks[2], cfg, dt, cross=True)
            return p
        if cfg.arch_type in ("ssm", "hybrid"):
            return {"ln1": jnp.zeros((cfg.d_model,), dt),
                    "ssm": init_ssm_params(ks[0], cfg, dt)}
        raise ValueError(cfg.arch_type)

    def init_params(self, key: jax.Array) -> dict:
        """Seeded random parameters, drawn by one jitted program per
        config: each weight's float32 draw fuses into its cast, so at
        published widths no float32 copy of a layer stack is ever held
        beside the model."""
        return _init_params(self.cfg, key)

    def _draw_params(self, key: jax.Array) -> dict:
        cfg, dt = self.cfg, self.dtype
        keys = jax.random.split(key, 8)
        params: dict[str, Any] = {
            "embed": dense_init(keys[0], (cfg.vocab, cfg.d_model), dt,
                                fan_in=cfg.d_model),
            "final_norm": jnp.zeros((cfg.d_model,), dt),
            "layers": _stack_init(keys[1], cfg.n_layers, self._init_block),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = dense_init(keys[2], (cfg.d_model, cfg.vocab),
                                           dt)
        if cfg.arch_type == "hybrid":
            k1, k2 = jax.random.split(keys[3])
            f = cfg.d_ff
            ka, kb, kc = jax.random.split(k2, 3)
            params["shared_attn"] = {
                "ln1": jnp.zeros((cfg.d_model,), dt),
                "ln2": jnp.zeros((cfg.d_model,), dt),
                "attn": init_attn_params(k1, cfg, dt),
                "mlp": {"w_gate": dense_init(ka, (cfg.d_model, f), dt),
                        "w_up": dense_init(kb, (cfg.d_model, f), dt),
                        "w_down": dense_init(kc, (f, cfg.d_model), dt,
                                             fan_in=f)}}
        if cfg.arch_type == "encdec":
            params["encoder"] = _stack_init(
                keys[4], cfg.encoder_layers,
                lambda k: self._enc_block(k))
            params["enc_norm"] = jnp.zeros((cfg.d_model,), dt)
        return params

    def _enc_block(self, key: jax.Array) -> dict:
        cfg, dt = self.cfg, self.dtype
        k0, k1 = jax.random.split(key)
        ka, kb, kc = jax.random.split(k1, 3)
        f = cfg.d_ff
        return {"ln1": jnp.zeros((cfg.d_model,), dt),
                "ln2": jnp.zeros((cfg.d_model,), dt),
                "attn": init_attn_params(k0, cfg, dt),
                "mlp": {"w_gate": dense_init(ka, (cfg.d_model, f), dt),
                        "w_up": dense_init(kb, (cfg.d_model, f), dt),
                        "w_down": dense_init(kc, (f, cfg.d_model), dt,
                                             fan_in=f)}}

    # ------------------------------------------------------------ primitives

    def _logits(self, params, h):
        cfg = self.cfg
        h = rms_norm(h, params["final_norm"], cfg.norm_eps)
        h = constrain_head_in(h)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        out = jnp.einsum("...d,dv->...v", h, head).astype(jnp.float32)
        return constrain_logits(out)

    def _mlp_or_moe(self, lp: dict, h: jax.Array):
        cfg = self.cfg
        hn = rms_norm(h, lp["ln2"], cfg.norm_eps)
        if cfg.arch_type == "moe":
            y, aux = moe_block(hn, lp["moe"], cfg)
            return h + y, aux
        return h + swiglu(hn, lp["mlp"]["w_gate"], lp["mlp"]["w_up"],
                          lp["mlp"]["w_down"]), jnp.float32(0.0)

    # --------------------------------------------------------------- encoder

    def _encode(self, params, frontend: jax.Array) -> jax.Array:
        """Whisper encoder over stub frame embeddings (B, F, D)."""
        cfg = self.cfg
        h = frontend.astype(self.dtype)

        def enc_layer(h, lp):
            a = attention_bidir(rms_norm(h, lp["ln1"], cfg.norm_eps),
                                lp["attn"], cfg)
            h = h + a
            h = h + swiglu(rms_norm(h, lp["ln2"], cfg.norm_eps),
                           lp["mlp"]["w_gate"], lp["mlp"]["w_up"],
                           lp["mlp"]["w_down"])
            return constrain(h), None

        fn = jax.checkpoint(enc_layer) if cfg.remat else enc_layer
        h, _ = lax.scan(fn, h, params["encoder"])
        return rms_norm(h, params["enc_norm"], cfg.norm_eps)

    # ---------------------------------------------------------- train forward

    def forward_train(self, params, batch: dict
                      ) -> tuple[jax.Array, jax.Array]:
        """batch: {"tokens": (B,S) int32, optional "frontend": (B,F,D)}.
        Returns (logits over the token positions, aux loss)."""
        cfg = self.cfg
        tokens = batch["tokens"]
        B, S = tokens.shape
        h = params["embed"][tokens]

        if cfg.arch_type == "encdec":
            enc_out = self._encode(params, batch["frontend"])

            def dec_layer(h, lp):
                a = attention_train(rms_norm(h, lp["ln1"], cfg.norm_eps),
                                    lp["attn"], cfg)
                h = h + a
                x = attention_cross(rms_norm(h, lp["ln_x"], cfg.norm_eps),
                                    lp["xattn"], cfg,
                                    *cross_kv(lp["xattn"], cfg, enc_out))
                h = h + x
                h, _ = self._mlp_or_moe(lp, h)
                return constrain(h), None

            fn = jax.checkpoint(dec_layer) if cfg.remat else dec_layer
            h, _ = lax.scan(fn, h, params["layers"])
            return self._logits(params, h), jnp.float32(0.0)

        prefix = 0
        if cfg.arch_type == "vlm":
            fe = batch["frontend"].astype(self.dtype)     # (B, P, D)
            prefix = fe.shape[1]
            h = jnp.concatenate([fe, h], axis=1)

        if cfg.arch_type in ("dense", "vlm", "moe"):
            def layer(h, lp):
                a = attention_train(rms_norm(h, lp["ln1"], cfg.norm_eps),
                                    lp["attn"], cfg, prefix_len=prefix)
                h = h + a
                h, aux = self._mlp_or_moe(lp, h)
                return constrain(h), aux

            fn = jax.checkpoint(layer) if cfg.remat else layer
            h, auxs = lax.scan(fn, h, params["layers"])
            logits = self._logits(params, h[:, prefix:] if prefix else h)
            return logits, jnp.sum(auxs)

        if cfg.arch_type == "ssm":
            def layer(h, lp):
                y, _ = ssm_block_train(rms_norm(h, lp["ln1"], cfg.norm_eps),
                                       lp["ssm"], cfg)
                return constrain(h + y), None

            fn = jax.checkpoint(layer) if cfg.remat else layer
            h, _ = lax.scan(fn, h, params["layers"])
            return self._logits(params, h), jnp.float32(0.0)

        if cfg.arch_type == "hybrid":
            h = self._hybrid_train(params, h)
            return self._logits(params, h), jnp.float32(0.0)

        raise ValueError(cfg.arch_type)

    def _hybrid_segments(self) -> tuple[int, int, int]:
        cfg = self.cfg
        every = cfg.attn_every or cfg.n_layers
        n_seg = cfg.n_layers // every
        rem = cfg.n_layers - n_seg * every
        return every, n_seg, rem

    def _hybrid_train(self, params, h):
        cfg = self.cfg
        every, n_seg, rem = self._hybrid_segments()

        def mamba_layer(h, lp):
            y, _ = ssm_block_train(rms_norm(h, lp["ln1"], cfg.norm_eps),
                                   lp["ssm"], cfg)
            return constrain(h + y), None

        fn = jax.checkpoint(mamba_layer) if cfg.remat else mamba_layer
        layers = params["layers"]
        seg_layers = jax.tree.map(
            lambda a: a[: n_seg * every].reshape(n_seg, every, *a.shape[1:]),
            layers)
        sp = params["shared_attn"]
        for s in range(n_seg):
            seg = jax.tree.map(lambda a: a[s], seg_layers)
            h, _ = lax.scan(fn, h, seg)
            a = attention_train(rms_norm(h, sp["ln1"], cfg.norm_eps),
                                sp["attn"], cfg)
            h = h + a
            h = h + swiglu(rms_norm(h, sp["ln2"], cfg.norm_eps),
                           sp["mlp"]["w_gate"], sp["mlp"]["w_up"],
                           sp["mlp"]["w_down"])
        if rem:
            tail = jax.tree.map(lambda a: a[n_seg * every:], layers)
            h, _ = lax.scan(fn, h, tail)
        return h

    # ------------------------------------------------------------------ cache

    def init_cache(self, batch: int, slots: int, ring: bool = False,
                   enc_frames: int = 0):
        cfg, dt = self.cfg, self.dtype
        if cfg.arch_type in ("dense", "vlm", "moe"):
            return init_attn_cache(cfg.n_layers, batch, slots,
                                   cfg.n_kv_heads, cfg.head_dim, dt, ring)
        if cfg.arch_type == "ssm":
            from .ssm import conv_dim
            return init_ssm_cache(cfg.n_layers, batch, cfg.ssm_conv,
                                  conv_dim(cfg), cfg.ssm_heads,
                                  cfg.ssm_head_dim, cfg.ssm_state, dt)
        if cfg.arch_type == "hybrid":
            from .ssm import conv_dim
            _, n_seg, _ = self._hybrid_segments()
            return HybridCacheT(
                ssm=init_ssm_cache(cfg.n_layers, batch, cfg.ssm_conv,
                                   conv_dim(cfg), cfg.ssm_heads,
                                   cfg.ssm_head_dim, cfg.ssm_state, dt),
                shared_attn=init_attn_cache(max(1, n_seg), batch, slots,
                                            cfg.n_kv_heads, cfg.head_dim,
                                            dt, ring))
        if cfg.arch_type == "encdec":
            frames = enc_frames or cfg.n_frontend_tokens
            return EncDecCache(
                self_attn=init_attn_cache(cfg.n_layers, batch, slots,
                                          cfg.n_kv_heads, cfg.head_dim, dt,
                                          ring),
                cross_k=jnp.zeros((cfg.n_layers, batch, frames,
                                   cfg.n_kv_heads, cfg.head_dim), dt),
                cross_v=jnp.zeros((cfg.n_layers, batch, frames,
                                   cfg.n_kv_heads, cfg.head_dim), dt))
        raise ValueError(cfg.arch_type)

    def init_paged_cache(self, batch: int, length: int, n_blocks: int,
                         block_size: int, quantize: bool = False,
                         ring: bool = False) -> PagedAttnCache:
        """Paged serving cache: shared (L, n_blocks, block_size, Hkv, hd)
        pool + (batch, ceil(length/block_size)) block tables. Attention
        families only (dense/moe); recurrent state has no positions to
        page."""
        cfg, dt = self.cfg, self.dtype
        assert cfg.arch_type in ("dense", "moe"), (
            f"paged KV supports dense/moe, not {cfg.arch_type}")
        return init_paged_attn_cache(cfg.n_layers, batch, length, n_blocks,
                                     block_size, cfg.n_kv_heads,
                                     cfg.head_dim, dt, quantize=quantize,
                                     ring=ring)

    # ------------------------------------------------------- decode / verify

    def decode_step(self, params, token: jax.Array, cache, pos: jax.Array,
                    window: int = 0, uniform_pos: bool = False):
        """token: (B,) int32; pos: (B,). Returns (logits (B,V), cache)."""
        logits, cache = self._window_step(params, token[:, None], cache, pos,
                                          window, uniform_pos=uniform_pos)
        return logits[:, -1, :], cache

    def verify_step(self, params, window_tokens: jax.Array, cache,
                    pos: jax.Array, window: int = 0,
                    seq_lens: Optional[jax.Array] = None,
                    uniform_pos: bool = False,
                    slot_off: Optional[jax.Array] = None,
                    pos_off: Optional[jax.Array] = None,
                    win_mask: Optional[jax.Array] = None):
        """window_tokens: (B, T). Returns (logits (B,T,V), cache).
        ``seq_lens`` — right-padded batches (prefill): valid length per
        sequence; exact identity-masking for recurrent (SSM) state.
        ``slot_off``/``pos_off``/``win_mask`` — tree-speculation window
        layout (dense/moe attention caches only; see
        :func:`repro.models.attention.attention_decode`)."""
        return self._window_step(params, window_tokens, cache, pos, window,
                                 seq_lens, uniform_pos=uniform_pos,
                                 slot_off=slot_off, pos_off=pos_off,
                                 win_mask=win_mask)

    def _window_step(self, params, tokens: jax.Array, cache, pos: jax.Array,
                     window: int = 0, seq_lens: Optional[jax.Array] = None,
                     uniform_pos: bool = False,
                     slot_off: Optional[jax.Array] = None,
                     pos_off: Optional[jax.Array] = None,
                     win_mask: Optional[jax.Array] = None):
        cfg = self.cfg
        B, T = tokens.shape
        h = params["embed"][tokens]
        w = window or 0
        tree_args = (slot_off is not None or pos_off is not None
                     or win_mask is not None)
        if tree_args and (isinstance(cache, PagedAttnCache)
                          or cfg.arch_type not in ("dense", "vlm", "moe")):
            raise NotImplementedError(
                "tree-speculation windows need a dense/moe AttnCache")

        if isinstance(cache, PagedAttnCache):
            # block_table is shared by all layers: closed over, not scanned
            bt, ring_, length_ = cache.block_table, cache.ring, cache.length
            if cache.quantized:
                def player(h, inp):
                    lp, kc, vc, ks, vs, pm = inp
                    a, kc, vc, ks, vs, pm = attention_decode_paged(
                        rms_norm(h, lp["ln1"], cfg.norm_eps), lp["attn"],
                        cfg, kc, vc, ks, vs, pm, bt, pos, ring_, length_, w)
                    h = h + a
                    h, _ = self._mlp_or_moe(lp, h)
                    return h, (kc, vc, ks, vs, pm)

                h, (k, v, ks, vs, pm) = lax.scan(
                    player, h, (params["layers"], cache.k, cache.v,
                                cache.k_scale, cache.v_scale, cache.pos_map))
                new_cache = cache.replace(k=k, v=v, k_scale=ks, v_scale=vs,
                                          pos_map=pm)
            else:
                def player(h, inp):
                    lp, kc, vc, pm = inp
                    a, kc, vc, _, _, pm = attention_decode_paged(
                        rms_norm(h, lp["ln1"], cfg.norm_eps), lp["attn"],
                        cfg, kc, vc, None, None, pm, bt, pos, ring_,
                        length_, w)
                    h = h + a
                    h, _ = self._mlp_or_moe(lp, h)
                    return h, (kc, vc, pm)

                h, (k, v, pm) = lax.scan(
                    player, h,
                    (params["layers"], cache.k, cache.v, cache.pos_map))
                new_cache = cache.replace(k=k, v=v, pos_map=pm)
            return self._logits(params, h), new_cache

        if cfg.arch_type in ("dense", "vlm", "moe"):
            # the stacked cache is loop state: layer l writes and reads its
            # row of the carried stack, which XLA updates in place (a
            # scanned xs -> ys stack is a second buffer the caller's loop
            # would copy back every step)
            def layer(carry, inp):
                h, k, v, pm = carry
                lp, l = inp
                a, k, v, pm = attention_decode(
                    rms_norm(h, lp["ln1"], cfg.norm_eps), lp["attn"], cfg,
                    k, v, pm, pos, cache.ring, w, uniform_pos,
                    slot_off=slot_off, pos_off=pos_off, win_mask=win_mask,
                    layer=l)
                h = h + a
                h, _ = self._mlp_or_moe(lp, h)
                return (h, k, v, pm), None

            (h, k, v, pm), _ = lax.scan(
                layer, (h, cache.k, cache.v, cache.pos_map),
                (params["layers"], jnp.arange(cfg.n_layers)))
            new_cache = AttnCache(k=k, v=v, pos_map=pm, ring=cache.ring)
            return self._logits(params, h), new_cache

        if cfg.arch_type == "ssm":
            return self._ssm_window(params, h, cache, T, seq_lens)

        if cfg.arch_type == "hybrid":
            return self._hybrid_window(params, h, cache, pos, T, w, seq_lens,
                                       uniform_pos)

        if cfg.arch_type == "encdec":
            def layer(h, inp):
                lp, kc, vc, pm, xk, xv = inp
                a, kc, vc, pm = attention_decode(
                    rms_norm(h, lp["ln1"], cfg.norm_eps), lp["attn"], cfg,
                    kc, vc, pm, pos, cache.self_attn.ring, w, uniform_pos)
                h = h + a
                x = attention_cross(rms_norm(h, lp["ln_x"], cfg.norm_eps),
                                    lp["xattn"], cfg, xk, xv)
                h = h + x
                h, _ = self._mlp_or_moe(lp, h)
                return h, (kc, vc, pm)

            sa = cache.self_attn
            h, (k, v, pm) = lax.scan(
                layer, h, (params["layers"], sa.k, sa.v, sa.pos_map,
                           cache.cross_k, cache.cross_v))
            new_cache = EncDecCache(
                self_attn=AttnCache(k=k, v=v, pos_map=pm, ring=sa.ring),
                cross_k=cache.cross_k, cross_v=cache.cross_v)
            return self._logits(params, h), new_cache

        raise ValueError(cfg.arch_type)

    def _ssm_window(self, params, h, cache: SSMCache, T: int,
                    seq_lens: Optional[jax.Array] = None):
        cfg = self.cfg

        if T == 1:
            def layer(h, inp):
                lp, conv, state = inp
                y, st = ssm_block_decode(
                    rms_norm(h, lp["ln1"], cfg.norm_eps), lp["ssm"], cfg,
                    SSDState(h=state, conv_tail=conv))
                return h + y, (st.conv_tail, st.h)
        else:
            def layer(h, inp):
                lp, conv, state = inp
                y, st = ssm_block_train(
                    rms_norm(h, lp["ln1"], cfg.norm_eps), lp["ssm"], cfg,
                    state=SSDState(h=state, conv_tail=conv),
                    seq_lens=seq_lens)
                return h + y, (st.conv_tail, st.h)

        h, (conv, state) = lax.scan(
            layer, h, (params["layers"], cache.conv, cache.state))
        return self._logits(params, h), SSMCache(conv=conv, state=state)

    def _hybrid_window(self, params, h, cache: HybridCacheT, pos, T: int,
                       w: int, seq_lens: Optional[jax.Array] = None,
                       uniform_pos: bool = False):
        cfg = self.cfg
        every, n_seg, rem = self._hybrid_segments()

        if T == 1:
            def mamba_layer(h, inp):
                lp, conv, state = inp
                y, st = ssm_block_decode(
                    rms_norm(h, lp["ln1"], cfg.norm_eps), lp["ssm"], cfg,
                    SSDState(h=state, conv_tail=conv))
                return h + y, (st.conv_tail, st.h)
        else:
            def mamba_layer(h, inp):
                lp, conv, state = inp
                y, st = ssm_block_train(
                    rms_norm(h, lp["ln1"], cfg.norm_eps), lp["ssm"], cfg,
                    state=SSDState(h=state, conv_tail=conv),
                    seq_lens=seq_lens)
                return h + y, (st.conv_tail, st.h)

        layers, ssm = params["layers"], cache.ssm
        sa, sp = cache.shared_attn, params["shared_attn"]
        seg = lambda a, s: jax.tree.map(
            lambda x: x[s * every:(s + 1) * every], a)
        convs, states = [], []
        ks, vs, pms = [], [], []
        for s in range(n_seg):
            h, (conv, state) = lax.scan(
                mamba_layer, h,
                (seg(layers, s), seg(ssm.conv, s), seg(ssm.state, s)))
            convs.append(conv)
            states.append(state)
            a, kc, vc, pm = attention_decode(
                rms_norm(h, sp["ln1"], cfg.norm_eps), sp["attn"], cfg,
                sa.k[s], sa.v[s], sa.pos_map[s], pos, sa.ring, w,
                uniform_pos)
            h = h + a
            h = h + swiglu(rms_norm(h, sp["ln2"], cfg.norm_eps),
                           sp["mlp"]["w_gate"], sp["mlp"]["w_up"],
                           sp["mlp"]["w_down"])
            ks.append(kc); vs.append(vc); pms.append(pm)
        if rem:
            tail = lambda a: jax.tree.map(lambda x: x[n_seg * every:], a)
            h, (conv, state) = lax.scan(
                mamba_layer, h,
                (tail(layers), tail(ssm.conv), tail(ssm.state)))
            convs.append(conv)
            states.append(state)
        new_cache = HybridCacheT(
            ssm=SSMCache(conv=jnp.concatenate(convs, axis=0),
                         state=jnp.concatenate(states, axis=0)),
            shared_attn=AttnCache(k=jnp.stack(ks), v=jnp.stack(vs),
                                  pos_map=jnp.stack(pms), ring=sa.ring))
        return self._logits(params, h), new_cache

    # ----------------------------------------------------------------- prefill

    def prefill(self, params, tokens: jax.Array, slots: int,
                frontend: Optional[jax.Array] = None, ring: bool = False,
                window: int = 0, prompt_lens: Optional[jax.Array] = None,
                chunk: Optional[int] = None, cache_shardings=None):
        """Process the whole prompt, build the serving cache.

        For attention families this routes through verify_step (cache-writing
        forward). For SSM/hybrid it runs the chunked scan. For encdec it also
        encodes the (stub) audio frames and precomputes cross-attention K/V.
        Returns (logits (B,S,V), cache).

        ``chunk``: long prompts process in ``chunk``-token pieces via a
        ``lax.scan`` with the cache as carry — attention scores stay
        O(chunk·S) instead of O(S²) (required for the 32k prefill shape).
        The chunked path returns logits for the LAST chunk only, shape
        (B, chunk, V) — serving needs just the anchor position."""
        cfg = self.cfg
        B, S = tokens.shape
        if cfg.arch_type != "ssm" and not ring and slots < S:
            # overflow writes are DROPPED, not clamped (models/kvcache.py):
            # refuse the geometry up front instead of silently losing the
            # prompt tail
            raise ValueError(
                f"prompt length {S} exceeds cache slots {slots}: size the "
                f"cache >= prompt + decode budget (or use a ring cache)")
        cache = self.init_cache(B, slots, ring=ring,
                                enc_frames=(frontend.shape[1]
                                            if frontend is not None and
                                            cfg.arch_type == "encdec" else 0))

        def pin(c):
            """Constrain the internally-built cache to the serving layout —
            without this XLA may replicate the batch dim of the scan-carried
            cache across the mesh (observed: an f32 full-cache temp)."""
            if cache_shardings is None:
                return c
            return jax.tree.map(
                lambda x, s: (jax.lax.with_sharding_constraint(x, s)
                              if isinstance(x, jax.Array) and hasattr(s, "spec")
                              else x),
                c, cache_shardings)

        cache = pin(cache)
        if cfg.arch_type == "encdec":
            enc_out = self._encode(params, frontend)

            def xkv(lp):
                return cross_kv(lp["xattn"], cfg, enc_out)
            xk, xv = jax.vmap(xkv)(params["layers"])
            cache = cache._replace(cross_k=xk, cross_v=xv)
        pos0 = jnp.zeros((B,), jnp.int32)
        if cfg.arch_type == "vlm" and frontend is not None:
            # Image prefix enters the cache first, then the text prompt.
            raise NotImplementedError(
                "vlm prefill with live frontend goes through serving.batching")
        if chunk and S > chunk and S % chunk == 0:
            assert prompt_lens is None, "chunked prefill takes full prompts"
            n = S // chunk
            blocks = jnp.moveaxis(tokens.reshape(B, n, chunk), 1, 0)

            def step(cache, inp):
                blk, idx = inp
                _, cache = self.verify_step(params, blk, cache,
                                            pos0 + idx * chunk, window,
                                            uniform_pos=True)
                return pin(cache), None

            cache, _ = lax.scan(step, cache,
                                (blocks[:-1], jnp.arange(n - 1)))
            # final chunk outside the scan so its logits survive; rewriting
            # its own cache slots is idempotent
            return self.verify_step(params, blocks[-1], cache,
                                    pos0 + (n - 1) * chunk, window,
                                    uniform_pos=True)
        # every row starts at 0: the prompt lands by one dynamic update
        # slice, not a scatter that would pin the fresh stack's layout
        return self.verify_step(params, tokens, cache, pos0, window,
                                seq_lens=prompt_lens,
                                uniform_pos=not ring or S <= slots)


@functools.partial(jax.jit, static_argnums=0)
def _init_params(cfg: ModelConfig, key: jax.Array) -> dict:
    return Model(cfg)._draw_params(key)


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg)
