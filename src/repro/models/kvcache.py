"""KV-cache structures: dense per-slot rows and the paged block pool.

Two attention-cache layouts share one masking mechanism:

- :class:`AttnCache` — the dense layout: every batch row ("slot") owns a
  max-length ``(L, B, S, Hkv, hd)`` allocation. Simple, and the reference
  the paged layout must match bit-for-bit, but capacity is priced at the
  worst-case sequence length even when most requests are short.
- :class:`PagedAttnCache` — the paged layout (vLLM-style): K/V live in a
  shared block pool ``(L, n_blocks, block_size, Hkv, hd)`` and each slot
  maps its *logical* positions ``0..length-1`` onto pool blocks through a
  per-slot int32 block table (``-1`` = unmapped). Admission allocates only
  the blocks a request's prompt + budget needs (:class:`BlockAllocator`);
  retirement frees them, so pool bytes buy admitted slots instead of
  padding. Optional int8 K/V halves block bytes again: each pool entry is
  quantized per (position, kv-head) over ``hd`` with the f32 scales stored
  alongside the blocks (``k_scale``/``v_scale``).

Both layouts carry an explicit ``pos_map`` of the absolute token position
written into each slot (dense) or pool entry (paged); ``-1`` = empty. This
one mechanism uniformly handles:

- ordinary append-at-pos decode,
- **ring-buffer** caches for sliding-window serving (logical slot =
  pos % length) — the TPU-native way to serve ``long_500k`` with bounded
  VMEM/HBM footprint,
- **speculative rollback**: rejected window entries simply keep a pos_map
  greater than the committed position and are masked out of attention until
  overwritten (see models/attention.py), so no cache truncation pass is
  needed after a rejected speculation window.

Speculative rollback × block reuse: the paged layout keeps rollback free
*only because* a slot's speculative window always lands inside its own
reserved blocks — admission reserves the full ``prompt + budget + 2γ``
footprint up front, so a rejected window never triggers an allocator call
and the stale entries are plain pos_map-masked pool entries. The converse
hazard is retirement: a retired slot's rows still receive (masked)
speculative window writes from the engine's frozen-slot step, so its block
table row MUST be scrubbed to ``-1`` (writes then drop) *before* its blocks
may be handed to another request — :func:`paged_release_slot`, dispatched
by ``DecodeSession.retire`` ahead of freeing the ids. Freed blocks may hold
stale pos_map entries; they are unreachable (no table points at them) and
the next insert fully rewrites pos_map for every block it maps.

Out-of-range writes (a sequence exceeding its cache/logical length) are
DROPPED in both layouts, never clamped: the dense non-ring path previously
clamped to the last slot, silently destroying the newest committed KV.
Callers are expected to size caches so this never fires (sessions
construct geometry from ``prompt + budget + 2γ + slack`` and assert it);
the drop is the safety net that keeps an overflow visible as a masked
(finite) error instead of silent corruption of a neighbour position.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp


class AttnCache(NamedTuple):
    """Stacked over layers: k,v (L, B, S, Hkv, hd); pos_map (L, B, S)."""
    k: jax.Array
    v: jax.Array
    pos_map: jax.Array
    ring: bool = False        # static: slot = pos % S when True

    @property
    def slots(self) -> int:
        return self.k.shape[2]


def init_attn_cache(n_layers: int, batch: int, slots: int, n_kv: int,
                    head_dim: int, dtype, ring: bool = False) -> AttnCache:
    return AttnCache(
        k=jnp.zeros((n_layers, batch, slots, n_kv, head_dim), dtype),
        v=jnp.zeros((n_layers, batch, slots, n_kv, head_dim), dtype),
        pos_map=jnp.full((n_layers, batch, slots), -1, jnp.int32),
        ring=ring)


# lanes of a TPU vector register: the minor dim of every (8, 128) tile
_LANES = 128


def update_layer_cache(k_cache: jax.Array, v_cache: jax.Array,
                       pos_map: jax.Array, k_new: jax.Array,
                       v_new: jax.Array, pos: jax.Array, ring,
                       uniform_pos: bool = False,
                       slot_off: Optional[jax.Array] = None,
                       pos_off: Optional[jax.Array] = None,
                       layer: Optional[jax.Array] = None
                       ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Write a (B, T, Hkv, hd) window into one layer's cache at per-sequence
    positions ``pos`` (B,). Returns updated (k, v, pos_map).

    ``layer`` (int32 scalar, may be traced): the caches are the whole
    (L, B, S, ...) stack a layer loop carries, and the window lands in
    layer ``layer``, in place. A head_dim that fills whole 128-lane rows
    keeps (Hkv, hd) minor in the TPU's layout, and the window's rows are
    scattered into the stack. A narrower head_dim (qwen2.5-0.5b's 64) is
    laid out with positions on the lanes, where a scatter would relay the
    whole stack out and back around every call; there each slot of the
    layer takes its window row if one lands on it, a select over the layer
    that attention reads anyway.

    Non-ring writes past the cache edge (``pos + t >= S``) are DROPPED —
    the cache keeps its newest committed KV instead of silently overwriting
    the last slot (the old ``min(pos, S-1)`` clamp). Ring writes wrap by
    construction and cannot overflow.

    ``slot_off``/``pos_off`` (each (T,) int32, non-ring only) decouple the
    write slot (``pos + slot_off[t]``) from the stored logical position
    (``pos + pos_off[t]``) — tree speculation places sibling branches in
    distinct slots that share a position. Default (None) keeps slot ==
    position == ``pos + t``, the linear layout.

    ``uniform_pos=True`` asserts all sequences share one position (aligned
    serving waves / prefill): the write lowers to a
    ``dynamic_update_slice``, which GSPMD partitions cleanly — the general
    per-sequence scatter forces an involuntary resharding/replication of the
    cache inside the decode loop (XLA spmd_partitioner limitation) and is
    kept only for ragged engine batches. Uniform positions make overflow
    all-or-nothing: an overflowing window writes back what its slots
    already hold."""
    B, T = k_new.shape[0], k_new.shape[1]
    S = pos_map.shape[-1]
    lead = () if layer is None else (layer,)
    if uniform_pos:
        assert slot_off is None and pos_off is None
        p0 = pos[0]
        # no wrap handling: a T-token window must not straddle the ring seam
        # (serving guarantees T=1 for ring caches; see launch/shapes.py)
        slot0 = jnp.where(ring, p0 % S, jnp.minimum(p0, S - T))
        abs_pos = (p0 + jnp.arange(T))[None, :].astype(jnp.int32) \
            + jnp.zeros((B, 1), jnp.int32)
        overflow = jnp.logical_and(jnp.logical_not(jnp.asarray(ring)),
                                   p0 + T > S)

        def write(c, new):
            new = new.reshape((1,) * len(lead) + new.shape)
            start = lead + (0, slot0) + (0,) * (new.ndim - len(lead) - 2)
            old = jax.lax.dynamic_slice(c, start, new.shape)
            return jax.lax.dynamic_update_slice(
                c, jnp.where(overflow, old, new), start)

        return (write(k_cache, k_new), write(v_cache, v_new),
                write(pos_map, abs_pos))
    if slot_off is not None or pos_off is not None:
        # ``ring`` may arrive as a traced scalar (sessions canonicalize the
        # static flag into an array); the static check only fires when the
        # flag is still concrete — ring sessions never reach the tree path
        # (the engine/session gates reject them first)
        assert not (isinstance(ring, bool) and ring), \
            "tree slot/pos decoupling needs a non-ring cache"
    s_off = jnp.arange(T) if slot_off is None else slot_off
    p_off = s_off if pos_off is None else pos_off
    abs_pos = pos[:, None] + p_off[None, :]                   # (B, T)
    # non-ring: an out-of-range position indexes past S and the scatter
    # drops it (mode="drop") instead of clamping onto slot S-1
    write_pos = pos[:, None] + s_off[None, :]
    slot = jnp.where(ring, write_pos % S, write_pos)

    if layer is not None and k_cache.shape[-1] % _LANES:
        hit = slot[:, :, None] == jnp.arange(S)                   # (B,T,S)
        # a window longer than a ring wraps onto its own slots: the last
        # row written to a slot wins, as in a sequential scatter
        t_idx = jnp.arange(T)[None, :, None]
        hit &= t_idx == jnp.max(jnp.where(hit, t_idx, -1), 1, keepdims=True)

        def select(c, new):
            old = c[layer]
            tail = (1,) * (old.ndim - 2)
            mask = hit.reshape(hit.shape + tail)
            # one window row at most is kept per slot: the sum is that row
            rows = jnp.where(mask, jnp.expand_dims(new, 2),
                             jnp.zeros((), new.dtype)).sum(1, new.dtype)
            written = mask.any(1)
            return jax.lax.dynamic_update_index_in_dim(
                c, jnp.where(written, rows, old), layer, 0)

        return (select(k_cache, k_new), select(v_cache, v_new),
                select(pos_map, abs_pos))
    batch_idx = jnp.arange(B)[:, None].repeat(T, axis=1)      # (B, T)
    idx = lead + (batch_idx, slot)
    k_cache = k_cache.at[idx].set(k_new, mode="drop")
    v_cache = v_cache.at[idx].set(v_new, mode="drop")
    pos_map = pos_map.at[idx].set(abs_pos, mode="drop")
    return k_cache, v_cache, pos_map


def _tree_commit_layer(k, v, pm, pos, path, n_acc, n_entries, d_max):
    """One layer of :func:`tree_commit_cache` — k/v (B,S,Hkv,hd), pm (B,S)."""
    B, S = pm.shape
    d_idx = jnp.arange(d_max)
    src = jnp.clip(pos[:, None] + path, 0, S - 1)             # (B, d_max)
    kg = jnp.take_along_axis(k, src[:, :, None, None], axis=1)
    vg = jnp.take_along_axis(v, src[:, :, None, None], axis=1)
    pg = jnp.take_along_axis(pm, src, axis=1)
    # Scrub the whole window region: losing branches AND stale tails; the
    # accepted path is re-scattered below. (Tree slots carry pos_map values
    # below their slot index, so the linear path's slot_pos<=q_pos masking
    # cannot be relied on here — the scrub makes staleness explicit.)
    s_idx = jnp.arange(S)[None, :]
    region = (s_idx > pos[:, None]) & (s_idx < pos[:, None] + n_entries)
    pm = jnp.where(region, -1, pm)
    valid = d_idx[None, :] < n_acc[:, None]
    dest = jnp.where(valid, pos[:, None] + 1 + d_idx[None, :], S)  # S ⇒ drop
    b_idx = jnp.broadcast_to(jnp.arange(B)[:, None], dest.shape)
    k = k.at[b_idx, dest].set(kg, mode="drop")
    v = v.at[b_idx, dest].set(vg, mode="drop")
    # A source entry the proposer never wrote (pg < 0, the draft's tail
    # hole) stays a hole after relocation instead of validating garbage KV.
    new_pm = jnp.where(pg >= 0, pos[:, None] + 1 + d_idx[None, :], -1)
    pm = pm.at[b_idx, dest].set(new_pm, mode="drop")
    return k, v, pm


def tree_commit_cache(cache: AttnCache, pos: jax.Array, path: jax.Array,
                      n_acc: jax.Array, n_entries: int) -> AttnCache:
    """Relocate a verified tree's winning path onto the canonical linear
    slots and scrub the losers (dense non-ring caches only).

    Tree entry ``e`` lives at slot ``pos + e`` with logical position
    ``pos + tree_pos[e]`` — after the verdict, accepted depth ``d`` of the
    winning path (entry ``path[:, d]``, ``d < n_acc``) must end up where
    the linear layout keeps it: slot ``pos + 1 + d`` with pos_map
    ``pos + 1 + d``. Everything else in ``(pos, pos + n_entries)`` gets
    its pos_map scrubbed to −1 (the pos_map rollback mechanism, plus the
    relocation the linear path never needs because its slots == positions).

    ``path`` entries at ``d >= n_acc`` are ignored (dropped scatter); done
    rows pass ``n_acc == 0`` and only scrub."""
    assert not (isinstance(cache.ring, bool) and cache.ring), \
        "tree speculation needs a non-ring dense cache"
    d_max = path.shape[1]
    k, v, pm = jax.vmap(
        _tree_commit_layer, in_axes=(0, 0, 0, None, None, None, None, None)
    )(cache.k, cache.v, cache.pos_map, pos, path, n_acc, n_entries, d_max)
    return cache._replace(k=k, v=v, pos_map=pm)


# --------------------------------------------------------------------------
# Paged attention cache: shared block pool + per-slot block tables
# --------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
class PagedAttnCache:
    """Paged KV storage for the attention families (dense/moe).

    Pool leaves (shared across slots):

    - ``k``/``v``:   (L, n_blocks, block_size, Hkv, hd) — model dtype, or
      int8 when quantized,
    - ``k_scale``/``v_scale``: (L, n_blocks, block_size, Hkv) f32 dequant
      scales, present only when quantized,
    - ``pos_map``:   (L, n_blocks, block_size) int32 absolute positions
      (−1 = empty), the same masking contract as :class:`AttnCache`.

    Per-slot mapping:

    - ``block_table``: (B, n_log) int32, shared by all layers; entry
      ``[b, i]`` is the pool block holding slot ``b``'s logical positions
      ``[i·bs, (i+1)·bs)``, or −1 (unmapped ⇒ writes drop, reads mask).

    ``ring`` and ``length`` are STATIC aux data (hashable, part of the jit
    signature): ``length`` is the logical sequence capacity — gathering a
    slot's blocks in logical order and slicing to ``length`` reproduces a
    dense ``AttnCache`` row exactly, which is what makes the paged decode
    path bit-identical to the dense one (same reduction lengths, same
    masking; see models/attention.py)."""

    def __init__(self, k, v, pos_map, block_table, ring: bool = False,
                 length: int = 0, k_scale=None, v_scale=None):
        self.k = k
        self.v = v
        self.pos_map = pos_map
        self.block_table = block_table
        self.ring = bool(ring)
        self.length = int(length)
        self.k_scale = k_scale
        self.v_scale = v_scale

    # pytree protocol: pool/table leaves are children, geometry is static
    def tree_flatten(self):
        return ((self.k, self.v, self.pos_map, self.block_table,
                 self.k_scale, self.v_scale), (self.ring, self.length))

    @classmethod
    def tree_unflatten(cls, aux, children):
        k, v, pos_map, block_table, k_scale, v_scale = children
        ring, length = aux
        return cls(k=k, v=v, pos_map=pos_map, block_table=block_table,
                   ring=ring, length=length, k_scale=k_scale,
                   v_scale=v_scale)

    def replace(self, **kw) -> "PagedAttnCache":
        cur = dict(k=self.k, v=self.v, pos_map=self.pos_map,
                   block_table=self.block_table, ring=self.ring,
                   length=self.length, k_scale=self.k_scale,
                   v_scale=self.v_scale)
        cur.update(kw)
        return PagedAttnCache(**cur)

    @property
    def n_blocks(self) -> int:
        return self.k.shape[1]

    @property
    def block_size(self) -> int:
        return self.k.shape[2]

    @property
    def n_logical_blocks(self) -> int:
        return self.block_table.shape[1]

    @property
    def slots(self) -> int:           # AttnCache parity (logical length)
        return self.length

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None


def logical_blocks(length: int, block_size: int) -> int:
    """Blocks needed to cover ``length`` logical positions."""
    return math.ceil(length / block_size)


def init_paged_attn_cache(n_layers: int, batch: int, length: int,
                          n_blocks: int, block_size: int, n_kv: int,
                          head_dim: int, dtype, quantize: bool = False,
                          ring: bool = False) -> PagedAttnCache:
    n_log = logical_blocks(length, block_size)
    kv_dtype = jnp.int8 if quantize else dtype
    scale = (jnp.zeros((n_layers, n_blocks, block_size, n_kv), jnp.float32)
             if quantize else None)
    return PagedAttnCache(
        k=jnp.zeros((n_layers, n_blocks, block_size, n_kv, head_dim),
                    kv_dtype),
        v=jnp.zeros((n_layers, n_blocks, block_size, n_kv, head_dim),
                    kv_dtype),
        pos_map=jnp.full((n_layers, n_blocks, block_size), -1, jnp.int32),
        block_table=jnp.full((batch, n_log), -1, jnp.int32),
        ring=ring, length=length, k_scale=scale,
        v_scale=None if scale is None else jnp.zeros_like(scale))


def quantize_kv(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-entry symmetric int8 over the head dim: x (..., hd) →
    (int8 (..., hd), f32 scale (...,))."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def _flat_pool(pool: jax.Array):
    """(L, NB, bs, ...) → (L, NB·bs, ...) so (block, offset) pairs address
    entries through one fused index."""
    L, NB, bs = pool.shape[:3]
    return pool.reshape(L, NB * bs, *pool.shape[3:])


def paged_update_layer(k_pool: jax.Array, v_pool: jax.Array,
                       k_scale: Optional[jax.Array],
                       v_scale: Optional[jax.Array],
                       pos_map: jax.Array, block_table: jax.Array,
                       k_new: jax.Array, v_new: jax.Array, pos: jax.Array,
                       ring: bool, length: int):
    """Write a (B, T, Hkv, hd) window into ONE layer's pool through the
    block table. Pool leaves here are single-layer: k/v (NB, bs, Hkv, hd),
    pos_map (NB, bs).

    Logical slot = pos (ring: pos % length); the write scatters into
    ``block_table[b, slot // bs] · bs + slot % bs`` of the flattened pool.
    Writes to unmapped blocks (table −1) or past ``length`` are DROPPED —
    mirroring the dense overflow-drop semantics, so a paged slot and a
    dense row diverge on nothing."""
    B, T = k_new.shape[0], k_new.shape[1]
    NB, bs = k_pool.shape[0], k_pool.shape[1]
    n_log = block_table.shape[1]
    abs_pos = pos[:, None] + jnp.arange(T)[None, :]           # (B, T)
    logical = jnp.where(ring, abs_pos % length, abs_pos)
    blk = logical // bs
    off = logical % bs
    phys = jnp.take_along_axis(block_table,
                               jnp.clip(blk, 0, n_log - 1), axis=1)
    invalid = (phys < 0) | (logical >= length) | (blk >= n_log)
    flat = jnp.where(invalid, NB * bs, phys * bs + off)       # OOB ⇒ drop

    if k_scale is not None:
        k_q, k_s = quantize_kv(k_new)
        v_q, v_s = quantize_kv(v_new)
        k_pool = _scatter_flat(k_pool, flat, k_q)
        v_pool = _scatter_flat(v_pool, flat, v_q)
        k_scale = _scatter_flat(k_scale, flat, k_s)
        v_scale = _scatter_flat(v_scale, flat, v_s)
    else:
        k_pool = _scatter_flat(k_pool, flat, k_new)
        v_pool = _scatter_flat(v_pool, flat, v_new)
    pm = pos_map.reshape(NB * bs)
    pm = pm.at[flat].set(abs_pos, mode="drop").reshape(NB, bs)
    return k_pool, v_pool, k_scale, v_scale, pm


def _scatter_flat(pool: jax.Array, flat: jax.Array, val: jax.Array):
    NB, bs = pool.shape[0], pool.shape[1]
    f = pool.reshape(NB * bs, *pool.shape[2:])
    return f.at[flat].set(val.astype(pool.dtype),
                          mode="drop").reshape(pool.shape)


def gather_layer_paged(k_pool: jax.Array, v_pool: jax.Array,
                       k_scale: Optional[jax.Array],
                       v_scale: Optional[jax.Array],
                       pos_map: jax.Array, block_table: jax.Array,
                       length: int, out_dtype):
    """Materialize ONE layer's logical dense view from the pool:
    k/v (B, length, Hkv, hd) in ``out_dtype`` plus pos (B, length).

    The view is position-ordered and sliced to exactly ``length`` entries,
    so downstream attention math is shape-identical (hence, for fp pools,
    bit-identical) to the dense path; unmapped positions read block 0 but
    surface pos −1 and are masked exactly like a dense empty slot."""
    NB, bs = k_pool.shape[0], k_pool.shape[1]
    j = jnp.arange(length)
    phys = block_table[:, j // bs]                            # (B, length)
    flat = jnp.clip(phys, 0, NB - 1) * bs + (j % bs)[None, :]
    kf = k_pool.reshape(NB * bs, *k_pool.shape[2:])
    vf = v_pool.reshape(NB * bs, *v_pool.shape[2:])
    k_d = kf[flat]
    v_d = vf[flat]
    if k_scale is not None:
        ks = k_scale.reshape(NB * bs, -1)[flat]               # (B, len, Hkv)
        vs = v_scale.reshape(NB * bs, -1)[flat]
        k_d = (k_d.astype(jnp.float32) * ks[..., None]).astype(out_dtype)
        v_d = (v_d.astype(jnp.float32) * vs[..., None]).astype(out_dtype)
    else:
        k_d = k_d.astype(out_dtype)
        v_d = v_d.astype(out_dtype)
    pm_d = jnp.where(phys >= 0, pos_map.reshape(NB * bs)[flat], -1)
    return k_d, v_d, pm_d


def paged_insert_row(pool: PagedAttnCache, row: AttnCache,
                     block_ids: jax.Array, slot) -> PagedAttnCache:
    """Admission: scatter a freshly prefilled DENSE cache row (batch 1,
    S == pool.length) into the pool blocks ``block_ids`` ((n_log,) int32,
    −1 = unreserved tail) and point ``block_table[slot]`` at them.

    Every mapped block gets its k/v/pos_map fully rewritten (the padded
    row tail carries pos −1), so a reused block can never leak its previous
    tenant's entries — scrub-on-alloc. ``slot`` and ``block_ids`` may be
    traced (one compiled insert program for any slot/any blocks)."""
    L = row.k.shape[0]
    S = row.k.shape[2]
    NB, bs = pool.n_blocks, pool.block_size
    n_log = block_ids.shape[0]
    padS = n_log * bs
    assert S <= padS, (S, padS)

    def blocks_of(x, fill):
        x = x[:, 0]                                    # (L, S, ...)
        pad = [(0, 0), (0, padS - S)] + [(0, 0)] * (x.ndim - 2)
        x = jnp.pad(x, pad, constant_values=fill)
        return x.reshape(L, n_log, bs, *x.shape[2:])

    idx = jnp.where(block_ids >= 0, block_ids, NB)     # −1 ⇒ dropped write
    k_b = blocks_of(row.k, 0)
    v_b = blocks_of(row.v, 0)
    pm_b = blocks_of(row.pos_map, -1)
    if pool.quantized:
        k_b, ks_b = quantize_kv(k_b)
        v_b, vs_b = quantize_kv(v_b)
        k_scale = pool.k_scale.at[:, idx].set(ks_b, mode="drop")
        v_scale = pool.v_scale.at[:, idx].set(vs_b, mode="drop")
    else:
        k_scale, v_scale = pool.k_scale, pool.v_scale
    k = pool.k.at[:, idx].set(k_b.astype(pool.k.dtype), mode="drop")
    v = pool.v.at[:, idx].set(v_b.astype(pool.v.dtype), mode="drop")
    pm = pool.pos_map.at[:, idx].set(pm_b, mode="drop")
    table = pool.block_table.at[slot].set(block_ids.astype(jnp.int32))
    return pool.replace(k=k, v=v, pos_map=pm, block_table=table,
                        k_scale=k_scale, v_scale=v_scale)


def paged_release_slot(pool: PagedAttnCache, slot) -> PagedAttnCache:
    """Retirement: unmap a slot's block table row (−1 ⇒ the frozen slot's
    ongoing speculative window writes drop). MUST run before the slot's
    blocks are returned to the allocator — see the module docstring on
    block reuse."""
    n_log = pool.n_logical_blocks
    return pool.replace(block_table=pool.block_table.at[slot].set(
        jnp.full((n_log,), -1, jnp.int32)))


class BlockAllocator:
    """Host-side free-list allocator over the pool's physical blocks.

    Blocks are unit-sized so there is no external fragmentation; the
    invariants the property tests pin down are (a) a block is never handed
    to two live reservations, (b) free + allocated always partition
    ``[0, n_blocks)``, (c) ``alloc`` fails exactly when fewer than ``n``
    blocks are free. LIFO reuse keeps recently-touched blocks hot."""

    def __init__(self, n_blocks: int):
        self.n_blocks = int(n_blocks)
        self._free = list(range(self.n_blocks - 1, -1, -1))  # pop() → 0 first
        self._used: set[int] = set()

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def used_blocks(self) -> int:
        return len(self._used)

    def alloc(self, n: int) -> list[int]:
        if n > len(self._free):
            raise RuntimeError(
                f"KV pool exhausted: need {n} blocks, {len(self._free)} free "
                f"of {self.n_blocks}")
        ids = [self._free.pop() for _ in range(n)]
        self._used.update(ids)
        return ids

    def free(self, ids) -> None:
        for i in ids:
            i = int(i)
            if i < 0:
                continue               # padded (unreserved) table entries
            assert i in self._used, f"double free of block {i}"
            self._used.remove(i)
            self._free.append(i)


class SSMCache(NamedTuple):
    """Mamba2 recurrent state, stacked over layers.

    conv:  (L, B, conv_width-1, conv_dim) — short-conv tail
    state: (L, B, n_heads, head_dim, d_state) — SSD state
    """
    conv: jax.Array
    state: jax.Array


def init_ssm_cache(n_layers: int, batch: int, conv_width: int, conv_dim: int,
                   n_heads: int, head_dim: int, d_state: int, dtype) -> SSMCache:
    return SSMCache(
        conv=jnp.zeros((n_layers, batch, conv_width - 1, conv_dim), dtype),
        state=jnp.zeros((n_layers, batch, n_heads, head_dim, d_state),
                        jnp.float32))


class HybridCache(NamedTuple):
    """Zamba2-style hybrid: SSM cache for the backbone + one shared
    attention cache reused at each shared-block invocation site."""
    ssm: SSMCache
    attn: AttnCache


# --------------------------------------------------------------------------
# Slot recycling (continuous batching)
#
# A serving DecodeSession keeps ONE live cache of fixed batch capacity and
# recycles batch rows ("slots") across requests: a finished request's slot
# is retired and a new prompt's freshly prefilled cache row is inserted in
# its place, without touching neighbouring rows. Both helpers are jittable
# with a traced ``slot`` index, so admission/retirement never recompiles.
# Paged caches recycle through block-map edits instead:
# paged_insert_row / paged_release_slot above.
# --------------------------------------------------------------------------

def insert_slot(dst, src, slot, batch_axis: int = 1):
    """Write batch row 0 of every array leaf of ``src`` into batch row
    ``slot`` of the matching leaf of ``dst``.

    Works on any cache pytree (:class:`AttnCache`, :class:`SSMCache`,
    :class:`HybridCache`, encdec caches, full ``SpecDecodeState`` trees):
    layer-stacked leaves carry batch on ``batch_axis`` (L, B, ...); rank-1
    leaves (per-sequence scalars like ``pos``/``last_token``) carry it on
    axis 0. Non-array leaves (the static ``ring`` flag) keep ``dst``'s
    value. ``slot`` may be a traced int32 — the write lowers to
    ``dynamic_update_index_in_dim``, one compiled program for any slot.
    Paged caches have mismatched pool/row structures and use
    :func:`paged_insert_row` instead."""
    def ins(d, s):
        if not isinstance(d, jax.Array) or d.ndim == 0:
            return d
        ax = batch_axis if d.ndim > batch_axis else 0
        row = jax.lax.index_in_dim(jnp.asarray(s), 0, axis=ax, keepdims=True)
        return jax.lax.dynamic_update_index_in_dim(
            d, row.astype(d.dtype), slot, axis=ax)
    return jax.tree.map(ins, dst, src)


def reset_slot(cache, slot, batch_axis: int = 1):
    """Scrub batch row ``slot`` of a cache pytree back to its init state:
    k/v/conv/state zeroed, ``pos_map`` re-filled with −1 (empty). Insertion
    already fully overwrites a slot, so this is hygiene for long-lived
    sessions (drops stale KV of retired requests) rather than a
    correctness requirement; the retire→re-admit tests assert both paths.
    Paged caches are left untouched here (their batch dim is the block
    table, handled by :func:`paged_release_slot`)."""
    def _scrub(node):
        if isinstance(node, tuple) and hasattr(node, "_fields"):
            vals = {}
            for name in node._fields:
                leaf = getattr(node, name)
                if isinstance(leaf, jax.Array) and leaf.ndim > 0:
                    ax = batch_axis if leaf.ndim > batch_axis else 0
                    fill = -1 if name == "pos_map" else 0
                    row = jnp.full_like(
                        jax.lax.index_in_dim(leaf, 0, axis=ax,
                                             keepdims=True), fill)
                    vals[name] = jax.lax.dynamic_update_index_in_dim(
                        leaf, row, slot, axis=ax)
                else:
                    vals[name] = _scrub(leaf) if isinstance(leaf, tuple) \
                        else leaf
            return type(node)(**vals)
        return node
    return _scrub(cache)
