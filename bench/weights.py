"""Seeded random weights, made by the benchmark, never by the program.

Every leaf is a pure function of (seed, model slot, layer, leaf): layer l
of a model draws from ``fold_in(fold_in(model_key, l), leaf_index)``.
:func:`program_params` makes a whole model's tree in the program's layout,
on the device, in one jitted call, in the type it is served in (bf16);
the plain reference draws the very same values one layer at a time
(:func:`layer_leaves`), so it takes nothing the program has made.

Distributions (assumed; random weights give α≈0 between two models):
matrices N(0, 1/fan_in), q/k/v biases N(0, 0.1²), RMSNorm weights
1 + 0.1·N(0, 1), the (tied) embedding N(0, 0.02²).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.spec import ModelDims

NORM_STD = 0.1
BIAS_STD = 0.1
EMBED_STD = 0.02


def seed_key(seed: int) -> jax.Array:
    """A threefry key from any non-negative seed up to 64 bits."""
    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} is outside [0, 2**64)")
    words = np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                     np.uint32)
    return jax.random.wrap_key_data(words, impl="threefry2x32")


def model_key(seed: int, slot: int) -> jax.Array:
    """Slot 0 is the target's key, slot 1 the draft's."""
    return jax.random.fold_in(seed_key(seed), slot)


def _leaf_specs(m: ModelDims) -> list[tuple[str, tuple, str, float]]:
    """(name, shape, kind, std) of one layer's leaves, in key order."""
    d, h, kv, hd, f = m.d_model, m.heads, m.kv_heads, m.head_dim, m.d_ff
    return [
        ("ln1", (d,), "norm", NORM_STD),
        ("wq", (d, h, hd), "normal", d ** -0.5),
        ("bq", (h, hd), "normal", BIAS_STD),
        ("wk", (d, kv, hd), "normal", d ** -0.5),
        ("bk", (kv, hd), "normal", BIAS_STD),
        ("wv", (d, kv, hd), "normal", d ** -0.5),
        ("bv", (kv, hd), "normal", BIAS_STD),
        ("wo", (h, hd, d), "normal", (h * hd) ** -0.5),
        ("ln2", (d,), "norm", NORM_STD),
        ("w_gate", (d, f), "normal", d ** -0.5),
        ("w_up", (d, f), "normal", d ** -0.5),
        ("w_down", (f, d), "normal", f ** -0.5),
    ]


def _draw(key, shape, kind, std, dtype):
    z = jax.random.normal(key, shape, jnp.float32)
    if kind == "norm":
        return (1.0 + std * z).astype(dtype)
    return (std * z).astype(dtype)


def layer_leaves(m: ModelDims, key: jax.Array, layer, dtype=jnp.bfloat16
                 ) -> dict:
    """Layer ``layer`` (may be traced) in published form: RMSNorm weights
    multiply, biases add."""
    kl = jax.random.fold_in(key, layer)
    return {name: _draw(jax.random.fold_in(kl, j), shape, kind, std, dtype)
            for j, (name, shape, kind, std) in enumerate(_leaf_specs(m))}


def global_leaves(m: ModelDims, key: jax.Array, dtype=jnp.bfloat16) -> dict:
    """The embedding (also the output head: Qwen2.5 ties them) and the
    final norm, drawn from keys past every layer's."""
    if not m.tied:
        raise NotImplementedError("an untied output head has no leaf here")
    embed = _draw(jax.random.fold_in(key, 2 ** 30), (m.vocab, m.d_model),
                  "normal", EMBED_STD, dtype)
    final = _draw(jax.random.fold_in(key, 2 ** 30 + 1), (m.d_model,),
                  "norm", NORM_STD, dtype)
    return {"embed": embed, "final_norm": final}


def _to_program_norm(w):
    # the program scales by (1 + w); w - 1 is exact for bf16 w in [0.5, 2]
    return (w.astype(jnp.float32) - 1.0).astype(w.dtype)


@functools.partial(jax.jit, static_argnums=0)
def _program_params(m: ModelDims, key: jax.Array) -> dict:
    def one(layer):
        lv = layer_leaves(m, key, layer)
        return {"ln1": _to_program_norm(lv["ln1"]),
                "ln2": _to_program_norm(lv["ln2"]),
                "attn": {k: lv[k] for k in ("wq", "bq", "wk", "bk", "wv",
                                            "bv", "wo")},
                "mlp": {k: lv[k] for k in ("w_gate", "w_up", "w_down")}}

    g = global_leaves(m, key)
    return {"embed": g["embed"],
            "final_norm": _to_program_norm(g["final_norm"]),
            "layers": jax.vmap(one)(jnp.arange(m.layers))}


def program_params(m: ModelDims, seed: int, slot: int) -> dict:
    """The model's whole tree in the program's layout (bf16, on device)."""
    if m.dtype != "bfloat16":
        raise NotImplementedError(f"served dtype {m.dtype}")
    return _program_params(m, model_key(seed, slot))
