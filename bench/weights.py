"""Seeded random weights, made by the benchmark, never by the program.

Every leaf is a pure function of (seed, model slot, layer, leaf): layer l
of a model draws leaf j of its family's ``leaf_specs`` from
``fold_in(fold_in(model_key, l), j)``, and global leaf j of its
``global_specs`` from ``fold_in(model_key, 2**30 + j)``, past every
layer's. The family's ``program_params`` makes a whole model's tree in the
program's layout, on the device, in one jitted call, in the type it is
served in (bf16); the plain reference draws the very same values one
layer at a time (:func:`layer_leaves`), so it takes nothing the program
has made.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import spec

GLOBAL_KEY = 2 ** 30


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


def _draw(key, shape, kind, std, dtype):
    z = jax.random.normal(key, shape, jnp.float32)
    if kind == "norm":
        return (1.0 + std * z).astype(dtype)
    return (std * z).astype(dtype)


def layer_leaves(m, key: jax.Array, layer, dtype=jnp.bfloat16) -> dict:
    """Layer ``layer`` (may be traced) in published form: RMSNorm weights
    multiply, biases add."""
    kl = jax.random.fold_in(key, layer)
    return {name: _draw(jax.random.fold_in(kl, j), shape, kind, std, dtype)
            for j, (name, shape, kind, std)
            in enumerate(spec.family_of(m).leaf_specs(m))}


def global_leaves(m, key: jax.Array, dtype=jnp.bfloat16) -> dict:
    """The leaves outside the layers, from keys past every layer's."""
    return {name: _draw(jax.random.fold_in(key, GLOBAL_KEY + j), shape,
                        kind, std, dtype)
            for j, (name, shape, kind, std)
            in enumerate(spec.family_of(m).global_specs(m))}


def program_norm(w):
    """An RMSNorm weight as the program keeps it: it scales by (1 + w);
    w - 1 is exact for bf16 w in [0.5, 2]."""
    return (w.astype(jnp.float32) - 1.0).astype(w.dtype)


def program_params(m, seed: int, slot: int) -> dict:
    """The model's whole tree in the program's layout (bf16, on device)."""
    if m.dtype != "bfloat16":
        raise NotImplementedError(f"served dtype {m.dtype}")
    return spec.family_of(m).program_params(m, model_key(seed, slot))
