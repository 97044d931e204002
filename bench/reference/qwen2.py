"""Plain Qwen2 forward pass: the reference that decides ``correct``.

Straight ``jax.numpy`` in float32 at ``precision=HIGHEST``, no cache, no
batching, no kernels, importing nothing of the program. It follows the
published Qwen2 decoder (hf:Qwen/Qwen2.5-3B): pre-RMSNorm blocks,
grouped-query attention with q/k/v biases and rotary embeddings
(rotate-half, base ``rope_theta``), SwiGLU MLP, final RMSNorm and an
output head tied to the embedding, or untied (``lm_head``). It takes the
``Dims`` of the family file ``bench/families/qwen2.py``.

The weights are drawn again from the seed, one layer at a time inside a
``lax.scan`` (:func:`bench.weights.layer_leaves`), so the whole model is
never resident in float32 and the reference fits on the chip after the
program's state is freed.

``quant="fp8"`` is the control: every weight matrix stored as
float8_e4m3fn with one scale per output channel (weight-only fp8, the
precision step below the configuration's bf16), all else as above.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from bench.weights import global_leaves, layer_leaves

HIGHEST = lax.Precision.HIGHEST
FP8_MAX = 448.0

# contraction axes of each matrix: the scale is per output channel
_CONTRACT = {"wq": (0,), "wk": (0,), "wv": (0,), "wo": (0, 1),
             "w_gate": (0,), "w_up": (0,), "w_down": (0,), "embed": (1,),
             "lm_head": (1,)}


def _fp8(w, axes):
    scale = jnp.max(jnp.abs(w), axis=axes, keepdims=True) / FP8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _weights(tree: dict, quant: str) -> dict:
    out = {}
    for k, w in tree.items():
        w = w.astype(jnp.float32)
        if quant == "fp8" and k in _CONTRACT:
            w = _fp8(w, _CONTRACT[k])
        elif quant not in ("none", "fp8"):
            raise ValueError(f"unknown quant {quant!r}")
        out[k] = w
    return out


def _rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: (T, H, hd) at positions 0..T-1, rotate-half convention."""
    t, hd = x.shape[0], x.shape[-1]
    half = hd // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _block(m, h, w):
    """One decoder layer over a batch of sequences h (S, T, d)."""
    t = h.shape[1]
    x = _rms(h, w["ln1"], m.norm_eps)
    q = jnp.einsum("std,dhk->sthk", x, w["wq"], precision=HIGHEST) + w["bq"]
    k = jnp.einsum("std,dhk->sthk", x, w["wk"], precision=HIGHEST) + w["bk"]
    v = jnp.einsum("std,dhk->sthk", x, w["wv"], precision=HIGHEST) + w["bv"]
    q = jax.vmap(_rope, (0, None))(q, m.rope_theta)
    k = jax.vmap(_rope, (0, None))(k, m.rope_theta)
    rep = m.heads // m.kv_heads
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("sthk,sjhk->shtj", q, k, precision=HIGHEST)
    s = s / jnp.sqrt(jnp.float32(m.head_dim))
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    s = jnp.where(causal[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("shtj,sjhk->sthk", p, v, precision=HIGHEST)
    h = h + jnp.einsum("sthk,hkd->std", o, w["wo"], precision=HIGHEST)
    x = _rms(h, w["ln2"], m.norm_eps)
    g = jnp.einsum("std,df->stf", x, w["w_gate"], precision=HIGHEST)
    u = jnp.einsum("std,df->stf", x, w["w_up"], precision=HIGHEST)
    return h + jnp.einsum("stf,fd->std", jax.nn.silu(g) * u, w["w_down"],
                          precision=HIGHEST)


@functools.partial(jax.jit, static_argnums=(0, 4))
def logits_at(m, key: jax.Array, tokens: jax.Array,
              out_pos: jax.Array, quant: str = "none") -> jax.Array:
    """Logits (S, n, vocab) of the model drawn from ``key`` over a batch
    of sequences ``tokens`` (S, T), read at positions ``out_pos`` (S, n).
    Padding after a sequence's last real token changes nothing before it
    (causal). Each layer's weights are drawn once for the whole batch."""
    g = _weights(global_leaves(m, key), quant)
    h = g["embed"][tokens]

    def layer(h, idx):
        return _block(m, h, _weights(layer_leaves(m, key, idx), quant)), None

    h, _ = lax.scan(layer, h, jnp.arange(m.layers))
    h = jnp.take_along_axis(h, out_pos[:, :, None], axis=1)
    x = _rms(h, g["final_norm"], m.norm_eps)
    head = g["lm_head"] if "lm_head" in g else g["embed"]
    return jnp.einsum("snd,vd->snv", x, head, precision=HIGHEST)
