"""A toy family for the tests: Qwen2's dense GQA block without q/k/v
biases, with an output head of its own (``lm_head``). The tests copy it
to ``bench/families/qwen2nobias.py`` beside a copy of the benchmark, to
show that a new architecture comes in by files alone.

Needed work as in the qwen2 family: the matmul weights read once and
multiplied by every token, attention over each slot's live positions,
each slot's live KV read once.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from bench import weights

BYTES = 2
NORM_STD = 0.1
EMBED_STD = 0.02


@dataclasses.dataclass(frozen=True)
class Dims:
    family: str
    name: str
    source: str
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    norm_eps: float
    tied: bool
    dtype: str

    def shrink(self, **widths) -> "Dims":
        return dataclasses.replace(self, **widths)

    def matmul_params(self) -> int:
        d, hd = self.d_model, self.head_dim
        per = d * hd * (2 * self.heads + 2 * self.kv_heads) \
            + 3 * d * self.d_ff
        return self.layers * per + self.vocab * d

    def params(self) -> int:
        per = self.d_model * self.head_dim * (2 * self.heads
                                              + 2 * self.kv_heads) \
            + 3 * self.d_model * self.d_ff + 2 * self.d_model
        emb = self.vocab * self.d_model * (1 if self.tied else 2)
        return emb + self.layers * per + self.d_model

    def kv_bytes(self, context: int) -> int:
        return context * self.layers * 2 * self.kv_heads * self.head_dim \
            * BYTES

    def pass_flops(self, tokens: int, contexts: list[int]) -> int:
        attn = 4 * self.layers * self.heads * self.head_dim * sum(contexts)
        return tokens * (2 * self.matmul_params() * len(contexts) + attn)

    def pass_bytes(self, tokens: int, contexts: list[int]) -> int:
        return self.matmul_params() * BYTES \
            + sum(self.kv_bytes(c) for c in contexts)


def dims(c: dict) -> Dims:
    return Dims(family=c["family"], name=c["name"], source=c["source"],
                layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                heads=c["num_attention_heads"],
                kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
                d_ff=c["intermediate_size"], vocab=c["vocab_size"],
                rope_theta=float(c["rope_theta"]),
                norm_eps=float(c["rms_norm_eps"]),
                tied=bool(c["tie_word_embeddings"]), dtype=c["torch_dtype"])


def leaf_specs(m: Dims) -> list:
    d, h, kv, hd, f = m.d_model, m.heads, m.kv_heads, m.head_dim, m.d_ff
    return [("ln1", (d,), "norm", NORM_STD),
            ("wq", (d, h, hd), "normal", d ** -0.5),
            ("wk", (d, kv, hd), "normal", d ** -0.5),
            ("wv", (d, kv, hd), "normal", d ** -0.5),
            ("wo", (h, hd, d), "normal", (h * hd) ** -0.5),
            ("ln2", (d,), "norm", NORM_STD),
            ("w_gate", (d, f), "normal", d ** -0.5),
            ("w_up", (d, f), "normal", d ** -0.5),
            ("w_down", (f, d), "normal", f ** -0.5)]


def global_specs(m: Dims) -> list:
    specs = [("embed", (m.vocab, m.d_model), "normal", EMBED_STD),
             ("final_norm", (m.d_model,), "norm", NORM_STD)]
    if not m.tied:
        specs.append(("lm_head", (m.vocab, m.d_model), "normal", EMBED_STD))
    return specs


@functools.partial(jax.jit, static_argnums=0)
def program_params(m: Dims, key: jax.Array) -> dict:
    def one(layer):
        lv = weights.layer_leaves(m, key, layer)
        return {"ln1": weights.program_norm(lv["ln1"]),
                "ln2": weights.program_norm(lv["ln2"]),
                "attn": {k: lv[k] for k in ("wq", "wk", "wv", "wo")},
                "mlp": {k: lv[k] for k in ("w_gate", "w_up", "w_down")}}

    g = weights.global_leaves(m, key)
    tree = {"embed": g["embed"],
            "final_norm": weights.program_norm(g["final_norm"]),
            "layers": jax.vmap(one)(jnp.arange(m.layers))}
    if not m.tied:
        tree["lm_head"] = g["lm_head"].T
    return tree


def program_config(m: Dims):
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=f"bench-{m.name}", arch_type="dense", n_layers=m.layers,
        d_model=m.d_model, n_heads=m.heads, n_kv_heads=m.kv_heads,
        d_ff=m.d_ff, vocab=m.vocab, head_dim=m.head_dim, qkv_bias=False,
        rope_theta=m.rope_theta, norm_eps=m.norm_eps,
        tie_embeddings=m.tied, dtype=m.dtype)
