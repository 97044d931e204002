"""The dense layer loop carries the stacked KV cache as loop state
(models/model.py, ``Model._window_step``): layer ``l`` writes its window
into row ``l`` of the carried (L, B, S, ...) stack, by a scatter of window
rows where head_dim fills 128 lanes and by a select over the layer where
it is narrower, and attends over that row. It must compute bit for bit
what ``attention_decode`` computes on one layer's cache at a time, slices
scanned in and restacked out: the same logits and the same
``k``/``v``/``pos_map``, for every window layout the serving paths use and
for a write that overflows and is dropped. (The oracle is a scan, not an
unrolled Python loop: XLA fuses an unrolled loop differently and moves
float32 logits by an ulp.)
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core.tree import TreeSpec
from repro.models.attention import attention_decode
from repro.models.kvcache import AttnCache
from repro.models.layers import rms_norm
from repro.models.model import build_model

CFG = ModelConfig(name="t", arch_type="dense", n_layers=3, d_model=32,
                  n_heads=4, n_kv_heads=2, d_ff=64, vocab=64,
                  dtype="float32", remat=False, qkv_bias=True)
WIDTHS = {"narrow": CFG, "lanes": dataclasses.replace(CFG, head_dim=128)}
B, S = 2, 16
TREE = TreeSpec(2, 2)

# case -> (ring, uniform_pos, tree, pos)
CASES = {
    "linear": (False, False, False, [5, 9]),
    "ring": (True, False, False, [14, 29]),        # both rows wrap
    "ring-lap": (True, False, False, [3, 7]),      # a window longer than S
    "uniform": (False, True, False, [6, 6]),
    "tree": (False, False, True, [4, 8]),
    "overflow": (False, False, False, [14, 3]),    # row 0 runs off the edge
    "uniform-overflow": (False, True, False, [14, 14]),
}


def _per_layer(model, params, tokens, cache, pos, **kw):
    """One layer's cache at a time: each layer's slice of the stack goes in
    as a scan input and comes out as a new stacked output."""
    cfg = model.cfg

    def layer(h, inp):
        lp, k, v, pm = inp
        a, k, v, pm = attention_decode(
            rms_norm(h, lp["ln1"], cfg.norm_eps), lp["attn"], cfg,
            k, v, pm, pos, cache.ring, 0, **kw)
        h, _ = model._mlp_or_moe(lp, h + a)
        return h, (k, v, pm)

    h, (k, v, pm) = jax.lax.scan(
        layer, params["embed"][tokens],
        (params["layers"], cache.k, cache.v, cache.pos_map))
    return model._logits(params, h), AttnCache(k=k, v=v, pos_map=pm,
                                               ring=cache.ring)


def _filled_cache(model, ring, seed):
    """A cache whose every layer holds distinct values and positions."""
    rng = np.random.default_rng(seed)
    c = model.init_cache(B, S, ring=ring)
    return c._replace(
        k=jnp.asarray(rng.normal(size=c.k.shape), jnp.float32),
        v=jnp.asarray(rng.normal(size=c.v.shape), jnp.float32),
        # slot s holds position s - 1, so every write changes pos_map
        pos_map=jnp.broadcast_to(jnp.arange(-1, S - 1, dtype=jnp.int32),
                                 c.pos_map.shape))


@pytest.mark.parametrize("width", list(WIDTHS))
@pytest.mark.parametrize("case,step", [
    (case, step) for case in CASES for step in ("decode", "verify")
    if not (CASES[case][2] and step == "decode")])   # trees only verify
def test_layer_loop_matches_per_layer_loop(case, step, width):
    ring, uniform, tree, pos = CASES[case]
    model = build_model(WIDTHS[width])
    params = model.init_params(jax.random.PRNGKey(0))
    cache = _filled_cache(model, ring, seed=len(case))
    pos = jnp.asarray(pos, jnp.int32)
    T = 1 if step == "decode" else (
        TREE.n_entries if tree else S + 4 if case == "ring-lap" else 4)
    tokens = jnp.asarray(
        np.random.default_rng(1).integers(0, CFG.vocab, (B, T)), jnp.int32)
    kw = {"uniform_pos": uniform}
    if tree:
        kw.update(slot_off=jnp.arange(T), pos_off=TREE.tree_pos,
                  win_mask=TREE.win_mask)

    if step == "decode":
        got = jax.jit(lambda c, p: model.decode_step(
            params, tokens[:, 0], c, p, uniform_pos=uniform))(cache, pos)
    else:
        got = jax.jit(lambda c, p: model.verify_step(
            params, tokens, c, p, **kw))(cache, pos)
    want_logits, want = jax.jit(lambda c, p: _per_layer(
        model, params, tokens, c, p, **kw))(cache, pos)
    if step == "decode":
        want_logits = want_logits[:, -1]

    logits, new = got
    np.testing.assert_array_equal(np.asarray(logits),
                                  np.asarray(want_logits))
    for name in ("k", "v", "pos_map"):
        np.testing.assert_array_equal(np.asarray(getattr(new, name)),
                                      np.asarray(getattr(want, name)))
    # the window really landed in every row, or, overflowing a uniform
    # write, was dropped whole
    changed = np.asarray(new.pos_map != cache.pos_map).all(axis=0).any(-1)
    dropped = uniform and int(pos[0]) + T > S
    assert changed.tolist() == [not dropped] * B
