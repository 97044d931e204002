"""Pallas TPU kernels for fused tree-verify (greedy, grid-family trees).

The tree verify hot-spot is extracting the target argmax at every tree
entry from the (B, T, V) logits of the single masked pass — V is far
beyond VMEM, so the vocab streams through in 128-aligned tiles exactly
like the linear verify kernels. Two passes:

- :func:`tree_argmax_kernel` — one sweep over the vocab per (batch,
  entry) row keeping a running (max, argmax) pair in VMEM scratch.
  Cross-tile ties break toward the LOWER vocab id (strict ``>`` update;
  in-tile ``argmax`` already ties-to-first) so the kernel matches
  ``jnp.argmax`` bit-for-bit — the contract
  :func:`repro.core.tree.verify_tree_greedy` is written against.
- :func:`tree_accept_kernel` — the longest-accepted-root-path rule on
  the (T,) target tokens: parent gathers become one-hot compares against
  an in-tile iota (no dynamic indexing), the ancestor-AND becomes a
  masked violation count over the (T, T) bitmap, and the winner/bonus
  come out of a one-hot reduction. All O(T²) on T = 1 + d_max·b_max ≤ a
  few dozen — pure VPU work on a single VMEM-resident block.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import resolve_interpret

NEG_INF = float("-inf")


def tree_argmax_kernel(p_ref, tgt_ref, acc_max, acc_idx):
    """Grid (B, V/TV); running argmax across vocab tiles in VMEM scratch.

    p: (1, T, TV) | tgt out (written at the last tile): (1, T, 1) i32 —
    a column, so the per-row reductions below never change layout.
    """
    vt = pl.program_id(1)

    @pl.when(vt == 0)
    def _init():
        acc_max[...] = jnp.full_like(acc_max, NEG_INF)
        acc_idx[...] = jnp.zeros_like(acc_idx)

    tv = p_ref.shape[-1]
    base = vt * tv
    p = p_ref[0, :, :].astype(jnp.float32)                 # (T, TV)
    local_max = jnp.max(p, axis=-1, keepdims=True)         # (T, 1)
    # first in-tile index attaining the max (ties → lowest id, as argmax)
    col = jax.lax.broadcasted_iota(jnp.int32, p.shape, 1)
    local_idx = base + jnp.min(jnp.where(p == local_max, col, tv), axis=-1,
                               keepdims=True)
    better = local_max > acc_max[...]                      # strict: keep
    acc_idx[...] = jnp.where(better, local_idx, acc_idx[...])  # earlier tile
    acc_max[...] = jnp.where(better, local_max, acc_max[...])  # on ties

    @pl.when(vt == pl.num_programs(1) - 1)
    def _done():
        tgt_ref[0] = acc_idx[...]


def tree_accept_kernel(tok_ref, tgt_ref, parent_ref, tpos_ref, valid_ref,
                       mask_ref, nacc_ref, winner_ref, bonus_ref):
    """Grid (B,); accept rule + winner selection on one sequence's tree.

    tok: (1, 1, T) i32 row | tgt: (1, T, 1) i32 column |
    parent / valid: (1, T) i32 rows, tpos: (T, 1) i32 column (shared) |
    mask: (T, T) i32 ancestor-or-self bitmap | outputs: (1, 1, 1) i32.
    Each operand arrives in the orientation its use needs, so no vector
    changes layout in-kernel.
    """
    T = mask_ref.shape[-1]
    tok = tok_ref[0]                                       # (1, T)
    tgt = tgt_ref[0]                                       # (T, 1)
    parent = parent_ref[...]                               # (1, T)
    tpos = tpos_ref[...]                                   # (T, 1)
    valid = valid_ref[...] > 0                             # (1, T)
    mask = mask_ref[...] > 0                               # (T, T)

    row = jax.lax.broadcasted_iota(jnp.int32, (T, T), 0)
    entry_row = jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
    entry_col = jax.lax.broadcasted_iota(jnp.int32, (T, 1), 0)

    # parent gather as a one-hot reduce: column c picks row parent[c]
    parent_tgt = jnp.sum(jnp.where(row == parent, tgt, 0), axis=0,
                         keepdims=True)                    # (1, T)
    match = (valid & (tok == parent_tgt)) | (entry_row == 0)   # anchor free
    # accept[e] = AND over ancestors-or-self of match ⇔ zero violations
    viol = jnp.sum(jnp.where(mask & ~match, 1, 0), axis=1,
                   keepdims=True)                          # (T, 1)
    accept = viol == 0

    # deepest accepted entry, ties → lowest entry index (best branch)
    score = jnp.where(accept, tpos * T + (T - entry_col), -1)
    best = jnp.max(score, axis=0, keepdims=True)           # (1, 1)
    w = jnp.min(jnp.where(score == best, entry_col, T), axis=0,
                keepdims=True)
    onehot_w = entry_col == w                              # (T, 1)
    nacc_ref[0] = jnp.sum(jnp.where(onehot_w, tpos, 0), axis=0,
                          keepdims=True)
    winner_ref[0] = w
    bonus_ref[0] = jnp.sum(jnp.where(onehot_w, tgt, 0), axis=0,
                           keepdims=True)


def tree_argmax_call(p_logits, tile: int, interpret=None):
    """(B, T, V) logits → (B, T) i32 per-entry target argmax."""
    interpret = resolve_interpret(interpret)
    B, T, V = p_logits.shape
    assert V % tile == 0, "ops.py pads the vocab to the tile size"
    tgt = pl.pallas_call(
        tree_argmax_kernel,
        grid=(B, V // tile),
        in_specs=[pl.BlockSpec((1, T, tile), lambda b, v: (b, 0, v))],
        out_specs=pl.BlockSpec((1, T, 1), lambda b, v: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, T, 1), jnp.int32),
        scratch_shapes=[pltpu.VMEM((T, 1), jnp.float32),
                        pltpu.VMEM((T, 1), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(p_logits)
    return tgt[:, :, 0]


def tree_accept_call(tree_tokens, tgt, parent, tpos, valid, mask,
                     interpret=None):
    """Per-batch accept/winner/bonus. ``tree_tokens``/``tgt`` are (B, T);
    the tree tables arrive as (1, T) / (T, T) i32 rows shared across the
    batch grid."""
    interpret = resolve_interpret(interpret)
    B, T = tree_tokens.shape
    row = pl.BlockSpec((1, 1, T), lambda b: (b, 0, 0))
    shared_row = pl.BlockSpec((1, T), lambda b: (0, 0))
    one = pl.BlockSpec((1, 1, 1), lambda b: (b, 0, 0))
    outs = pl.pallas_call(
        tree_accept_kernel,
        grid=(B,),
        in_specs=[
            row,                                              # tokens
            pl.BlockSpec((1, T, 1), lambda b: (b, 0, 0)),     # argmax col
            shared_row,                                       # parent
            pl.BlockSpec((T, 1), lambda b: (0, 0)),           # tree depth
            shared_row,                                       # valid
            pl.BlockSpec((T, T), lambda b: (0, 0)),           # ancestors
        ],
        out_specs=[one] * 3,
        out_shape=[jax.ShapeDtypeStruct((B, 1, 1), jnp.int32)] * 3,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(tree_tokens[:, None, :], tgt[:, :, None], parent,
      tpos.reshape(T, 1), valid, mask)
    n_acc, winner, bonus = outs
    return n_acc[:, 0, 0], winner[:, 0, 0], bonus[:, 0, 0]
