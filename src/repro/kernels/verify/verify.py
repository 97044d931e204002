"""Pallas TPU kernels for fused speculative-window verification.

The verification hot-spot streams the (γ, V) target/draft probability rows
through VMEM in 128-aligned vocab tiles (V is 100k–256k for the assigned
archs — far beyond VMEM, so HBM→VMEM tiling is mandatory). Two passes:

- :func:`gather_reduce_kernel` — one sweep over (p, q): gathers p/q at the
  draft-token ids (one-hot compare against an in-tile iota, no dynamic HBM
  gathers — TPU-friendly) and reduces the per-position residual mass
  Σ_v max(p−q, 0).
- :func:`cdf_sample_kernel` — a second sweep over the *single* selected row
  per sequence (scalar-prefetch row index): running-cumsum inverse-CDF
  threshold crossing, emitting the corrected/bonus token.

Elementwise/VPU-bound (no MXU): block shapes keep the lane dimension at a
multiple of 128 and the sublane at γ(+1) rows. The GPU version of this op
materializes full (B, γ, V) residual tensors; the TPU adaptation never
materializes them in HBM (DESIGN.md §3).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from .. import resolve_interpret

VOCAB_TILE = 512


def gather_reduce_kernel(tokens_ref, p_ref, q_ref,
                         p_at_ref, q_at_ref, mass_ref,
                         acc_p, acc_q, acc_m):
    """Grid (B, V/TV); accumulates across vocab tiles in VMEM scratch.

    tokens: (1, γ) i32 | p: (1, γ+1, TV) | q: (1, γ, TV)
    outputs (written at the last tile): p_at/q_at/mass (1, γ).
    """
    vt = pl.program_id(1)

    @pl.when(vt == 0)
    def _init():
        acc_p[...] = jnp.zeros_like(acc_p)
        acc_q[...] = jnp.zeros_like(acc_q)
        acc_m[...] = jnp.zeros_like(acc_m)

    tv = p_ref.shape[-1]
    gamma = q_ref.shape[1]
    base = vt * tv
    vocab_ids = base + jax.lax.broadcasted_iota(jnp.int32, (gamma, tv), 1)
    tok = tokens_ref[0, :][:, None]                     # (γ, 1)
    onehot = (vocab_ids == tok)                         # (γ, TV)

    p = p_ref[0, :gamma, :].astype(jnp.float32)         # (γ, TV)
    q = q_ref[0, :, :].astype(jnp.float32)              # (γ, TV)
    acc_p[...] += jnp.sum(jnp.where(onehot, p, 0.0), axis=-1)
    acc_q[...] += jnp.sum(jnp.where(onehot, q, 0.0), axis=-1)
    acc_m[...] += jnp.sum(jnp.maximum(p - q, 0.0), axis=-1)

    @pl.when(vt == pl.num_programs(1) - 1)
    def _done():
        p_at_ref[0, :] = acc_p[...]
        q_at_ref[0, :] = acc_q[...]
        mass_ref[0, :] = acc_m[...]


def cdf_sample_kernel(jrow_ref, qrow_ref, use_p_ref,     # scalar prefetch
                      p_ref, q_ref, thresh_ref,
                      token_ref, cum, found):
    """Grid (B, V/TV); inverse-CDF over the selected distribution row.

    p: (1, 1, TV) — row jrow[b] via scalar-prefetch index map
    q: (1, 1, TV) — row qrow[b]
    thresh: (1, 1) f32 — r·mass, precomputed by ops glue
    token out: (1, 1) i32
    """
    b = pl.program_id(0)
    vt = pl.program_id(1)
    tv = p_ref.shape[-1]

    @pl.when(vt == 0)
    def _init():
        cum[...] = jnp.zeros_like(cum)
        found[...] = jnp.full_like(found, -1)

    p = p_ref[0, 0, :].astype(jnp.float32)
    q = q_ref[0, 0, :].astype(jnp.float32)
    dist = jnp.where(use_p_ref[b] > 0, p, jnp.maximum(p - q, 0.0))
    local_cdf = jnp.cumsum(dist) + cum[0, 0]
    hit = local_cdf > thresh_ref[0, 0]
    any_hit = jnp.any(hit)
    local_idx = jnp.argmax(hit).astype(jnp.int32)

    @pl.when((found[0, 0] < 0) & any_hit)
    def _record():
        found[0, 0] = vt * tv + local_idx

    cum[0, 0] = local_cdf[-1]

    @pl.when(vt == pl.num_programs(1) - 1)
    def _done():
        # degenerate all-zero distribution → clamp to the final vocab id
        token_ref[0, 0] = jnp.where(found[0, 0] < 0,
                                    pl.num_programs(1) * tv - 1,
                                    found[0, 0])


def gather_reduce_call(tokens, p, q, tile: int = VOCAB_TILE,
                       interpret=None):
    interpret = resolve_interpret(interpret)  # None → compiled on TPU only
    B, gamma = tokens.shape
    V = p.shape[-1]
    assert V % tile == 0, "ops.py pads the vocab to the tile size"
    grid = (B, V // tile)
    return pl.pallas_call(
        gather_reduce_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, gamma), lambda b, v: (b, 0)),
            pl.BlockSpec((1, gamma + 1, tile), lambda b, v: (b, 0, v)),
            pl.BlockSpec((1, gamma, tile), lambda b, v: (b, 0, v)),
        ],
        out_specs=[
            pl.BlockSpec((1, gamma), lambda b, v: (b, 0)),
            pl.BlockSpec((1, gamma), lambda b, v: (b, 0)),
            pl.BlockSpec((1, gamma), lambda b, v: (b, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((B, gamma), jnp.float32)] * 3,
        scratch_shapes=[pltpu.VMEM((gamma,), jnp.float32)] * 3,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(tokens, p, q)


def cdf_sample_call(jrow, qrow, use_p, p, q, thresh, tile: int = VOCAB_TILE,
                    interpret=None):
    interpret = resolve_interpret(interpret)
    B = jrow.shape[0]
    V = p.shape[-1]
    assert V % tile == 0
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, V // tile),
        in_specs=[
            pl.BlockSpec((1, 1, tile), lambda b, v, jr, qr, up: (b, jr[b], v)),
            pl.BlockSpec((1, 1, tile), lambda b, v, jr, qr, up: (b, qr[b], v)),
            pl.BlockSpec((1, 1), lambda b, v, jr, qr, up: (b, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1), lambda b, v, jr, qr, up: (b, 0)),
        scratch_shapes=[pltpu.VMEM((1, 1), jnp.float32),
                        pltpu.VMEM((1, 1), jnp.int32)],
    )
    return pl.pallas_call(
        cdf_sample_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, 1), jnp.int32),
        interpret=interpret,
    )(jrow, qrow, use_p, p, q, thresh)
