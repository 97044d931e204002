"""The Qwen2 family (hf:Qwen/Qwen2.5-3B): pre-RMSNorm dense decoder
blocks, grouped-query attention with q/k/v biases and rotary embeddings,
a SwiGLU MLP, a final RMSNorm, and an output head tied to the embedding
or a leaf of its own.

A family file gives the benchmark everything that depends on the
architecture (:func:`bench.spec.family_module`): :func:`dims` and its
:class:`Dims`, the leaves the weights are drawn from (:func:`leaf_specs`,
:func:`global_specs`), the program's parameter tree
(:func:`program_params`) and its ``ModelConfig`` (:func:`program_config`).

Needed work of one forward of ``tokens`` window tokens on each active
slot, whose live contexts are ``contexts`` (:meth:`Dims.pass_flops`,
:meth:`Dims.pass_bytes`): the matmul weights (every layer's projections
and the output head; the embedding lookup is a gather) are read once and
multiplied by every token, 2·P FLOPs a token; each token attends over
its slot's live positions, 4·L·H·hd·c FLOPs, and each slot's live KV is
read once. The width the program pads to, copies of caches and logits at
positions no token needs are waste and are not counted, so a share
computed from these counts cannot exceed 100% unless the device time
leaves work out.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from bench import weights

BYTES = 2           # bf16 weights and KV

# Distributions (assumed; random weights give α≈0 between two models):
# matrices N(0, 1/fan_in), q/k/v biases N(0, 0.1²), RMSNorm weights
# 1 + 0.1·N(0, 1), the embedding and an untied output head N(0, 0.02²)
# (the published ``initializer_range``).
NORM_STD = 0.1
BIAS_STD = 0.1
EMBED_STD = 0.02


@dataclasses.dataclass(frozen=True)
class Dims:
    """One model at its published widths (HF ``config.json`` names)."""
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
        """A reduced-width copy, for CPU rehearsals and tests."""
        return dataclasses.replace(self, **widths)

    def layer_params(self) -> int:
        d, hd = self.d_model, self.head_dim
        attn = d * hd * (2 * self.heads + 2 * self.kv_heads) \
            + hd * (self.heads + 2 * self.kv_heads)
        return attn + 3 * d * self.d_ff + 2 * d

    def params(self) -> int:
        emb = self.vocab * self.d_model * (1 if self.tied else 2)
        return emb + self.layers * self.layer_params() + self.d_model

    def matmul_params(self) -> int:
        """Weights one token multiplies by."""
        d, hd = self.d_model, self.head_dim
        per = d * hd * (2 * self.heads + 2 * self.kv_heads) \
            + 3 * d * self.d_ff
        return self.layers * per + self.vocab * d

    def kv_bytes(self, context: int) -> int:
        """KV bytes of one slot at ``context`` positions (every layer
        attends over the whole context)."""
        return context * self.layers * 2 * self.kv_heads * self.head_dim \
            * BYTES

    def pass_flops(self, tokens: int, contexts: list[int]) -> int:
        attn = 4 * self.layers * self.heads * self.head_dim * sum(contexts)
        return tokens * (2 * self.matmul_params() * len(contexts) + attn)

    def pass_bytes(self, tokens: int, contexts: list[int]) -> int:
        return self.matmul_params() * BYTES \
            + sum(self.kv_bytes(c) for c in contexts)


def dims(c: dict) -> Dims:
    """The model file's numbers (with its ``name``) as :class:`Dims`."""
    return Dims(family=c["family"], name=c["name"], source=c["source"],
                layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                heads=c["num_attention_heads"],
                kv_heads=c["num_key_value_heads"],
                head_dim=c.get("head_dim",
                               c["hidden_size"] // c["num_attention_heads"]),
                d_ff=c["intermediate_size"], vocab=c["vocab_size"],
                rope_theta=float(c["rope_theta"]),
                norm_eps=float(c["rms_norm_eps"]),
                tied=bool(c["tie_word_embeddings"]), dtype=c["torch_dtype"])


def leaf_specs(m: Dims) -> list[tuple[str, tuple, str, float]]:
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


def global_specs(m: Dims) -> list[tuple[str, tuple, str, float]]:
    """The embedding, the final norm and, untied, the output head
    (published (vocab, d_model) layout)."""
    specs = [("embed", (m.vocab, m.d_model), "normal", EMBED_STD),
             ("final_norm", (m.d_model,), "norm", NORM_STD)]
    if not m.tied:
        specs.append(("lm_head", (m.vocab, m.d_model), "normal", EMBED_STD))
    return specs


@functools.partial(jax.jit, static_argnums=0)
def program_params(m: Dims, key: jax.Array) -> dict:
    """The whole tree in the program's layout, bf16, in one jitted call."""
    def one(layer):
        lv = weights.layer_leaves(m, key, layer)
        return {"ln1": weights.program_norm(lv["ln1"]),
                "ln2": weights.program_norm(lv["ln2"]),
                "attn": {k: lv[k] for k in ("wq", "bq", "wk", "bk", "wv",
                                            "bv", "wo")},
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
        d_ff=m.d_ff, vocab=m.vocab, head_dim=m.head_dim, qkv_bias=True,
        rope_theta=m.rope_theta, norm_eps=m.norm_eps,
        tie_embeddings=m.tied, dtype=m.dtype)
