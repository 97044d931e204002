"""Distributed speculative-decoding engine on *real* JAX models.

This is the execution layer the simulator abstracts: an edge draft model and
a cloud target model exchanging speculation windows (Fig. 1b). Two ways to
run the exchange:

- **colocated** (default): one fused jitted step per iteration; any network
  hop is accounted virtually (``rtt_ms`` on a virtual clock).
- **distributed** (:meth:`SpecDecodeEngine.split_workers` +
  :mod:`repro.distributed`): the step is split into a draft-side propose
  program and a target-side verify/commit program whose token/verdict
  payloads cross a ``Transport`` — zero-delay in process (bit-identical to
  the colocated path at temperature 0) or over an emulated edge–cloud link
  with measured wall-clock delays that feed the AWC ``rtt_recent_ms``
  feature.

Either way *acceptance outcomes are real* — this engine is what captures
the ground-truth ``acceptance_seq`` traces DSD-Sim replays (DESIGN.md
§7.3).

Decode hot loop — compiled ONCE, adaptive-γ AND continuous batching for
free:

- One XLA program per draft/target pair, compiled at the static window
  bound ``gamma_max``. The per-iteration window size γ chosen by the window
  policy (AWC changes it every iteration) enters as a *traced* int32
  ``active_gamma`` that masks acceptance in ``verify_window`` — any
  γ ∈ [1, gamma_max] runs with zero recompiles. At temperature 0 causality
  makes the masked step's committed tokens BIT-identical to a dedicated
  per-γ program; sampled decoding (temperature > 0) is identical in
  distribution but consumes the PRNG stream at gamma_max width, so
  individual sampled tokens differ from a per-γ program run with the same
  key. (The MoE family is the other caveat: capacity-based routing sees
  the full batch × full-width window, so capacity-binding configs may drop
  tokens differently depending on co-tenants.)
- The same program is *slot-aware*: every batch row carries a per-slot
  token budget (``max_new``) and a ``done`` flag, and
  :func:`repro.core.specdec.slot_stop_mask` zeroes ``num_new`` for
  finished/free rows so their cursor, position, KV writes and recurrent
  state freeze while neighbouring rows keep decoding. This is what lets
  :class:`repro.core.session.DecodeSession` admit and retire requests
  in-flight (continuous batching) without ever recompiling: the active-slot
  pattern is data, not shape.
- ``SpecDecodeState`` caches, the output token buffer, the write cursors
  and the stats buffers are DONATED to the jitted step
  (``donate_argnums``) so KV/SSM buffers update in place instead of copying
  every iteration. The dense layer loop and the draft's γ scan carry the
  stacked KV cache as loop state (models/model.py), so the donated stack is
  the one buffer every draft token and the verify write into, and it
  aliases the step's output.
- Committed tokens accumulate into a preallocated on-device
  ``(B, max_new)`` buffer with per-sequence write cursors; per-iteration
  ``n_accepted``/``num_new`` land in device-side ring buffers. The host
  syncs cursors/stats only every ``sync_every`` iterations, so the loop
  keeps ``sync_every`` steps in flight instead of blocking on
  ``new_tokens`` / ``num_new`` transfers per step. Window-policy features
  (recent α, TPOT) and admission/retirement decisions consequently happen
  at sync granularity.

Cache-rollback semantics per family:

- attention families (dense/moe/vlm/encdec): stale window entries are
  masked via ``pos_map`` (models/kvcache.py) — single fused
  :func:`repro.core.specdec.spec_decode_step`.
- ssm/hybrid: the recurrent state cannot be masked retroactively; the
  engine keeps the window-start state as the checkpoint, verifies on a
  throwaway copy, then *advances* the committed prefix with per-sequence
  active-masking (``_tree_where``) — the SSM analogue of cache rollback.
  The advance is a ``lax.scan`` over the window, so HLO size and compile
  time stay flat in ``gamma_max``.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..configs.base import ModelConfig
from ..models.kvcache import (PagedAttnCache, insert_slot, paged_insert_row,
                              paged_release_slot)
from ..models.model import build_model
from .specdec import (SpecDecodeOut, SpecDecodeState, draft_propose,
                      slot_stop_mask, spec_decode_step, verify_window,
                      verify_window_greedy, _temperature_probs,
                      sample_from_probs)
from .window import StaticWindowPolicy, WindowPolicy


def _tree_where(active: jax.Array, new: Any, old: Any, batch_axis: int = 1):
    """Per-sequence select over cache pytrees; non-array leaves pass through.

    ``active``: (B,) bool. Cache leaves carry batch on ``batch_axis``
    (layer-stacked caches are (L, B, ...))."""
    def sel(n, o):
        if not isinstance(n, jax.Array) or n.ndim == 0:
            return o
        shape = [1] * n.ndim
        ax = batch_axis if n.ndim > batch_axis else 0
        shape[ax] = active.shape[0]
        return jnp.where(active.reshape(shape), n, o)
    return jax.tree.map(sel, new, old)


def _scan_cache_advance(decode_fn, params, cache, adv_tokens: jax.Array,
                        pos: jax.Array, num_new: jax.Array):
    """Advance a recurrent cache over the committed window with ``lax.scan``.

    ``adv_tokens``: (B, T); step t feeds token t at position pos+t and keeps
    the updated cache only for sequences with t < num_new. Non-array cache
    leaves (e.g. the static ``ring`` flag) stay out of the scan carry so
    their treatment as static metadata survives the loop.
    """
    leaves, treedef = jax.tree.flatten(cache)
    is_arr = [isinstance(l, jax.Array) for l in leaves]

    def pack(c):
        return [l for l, a in zip(jax.tree.leaves(c), is_arr) if a]

    def unpack(arrs):
        it = iter(arrs)
        return jax.tree.unflatten(
            treedef, [next(it) if a else l for l, a in zip(leaves, is_arr)])

    toks = jnp.moveaxis(adv_tokens, 0, 1)          # (T, B)
    steps = jnp.arange(adv_tokens.shape[1])

    def body(carry, inp):
        tok, t = inp
        cur = unpack(carry)
        _, cnew = decode_fn(params, tok, cur, pos + t)
        cnew = _tree_where(t < num_new, cnew, cur)
        return pack(cnew), None

    out, _ = lax.scan(body, pack(cache), (toks, steps))
    return unpack(out)


def _accumulate(res: SpecDecodeOut, out_buf: jax.Array, cursor: jax.Array,
                nacc_buf: jax.Array, nn_buf: jax.Array, row_idx: jax.Array):
    """Scatter this iteration's committed tokens into the device-resident
    output buffer at per-sequence cursors; record n_accepted / num_new in
    row ``row_idx`` of the stats ring buffers (num_new == 0 marks a slot
    that was inactive this iteration — the host uses it to attribute
    acceptance bits to the right request). Writes past the buffer edge are
    dropped — those tokens are beyond ``max_new`` and would be discarded on
    extraction."""
    B, W = res.new_tokens.shape
    cap = out_buf.shape[1]
    widx = cursor[:, None] + jnp.arange(W)[None, :]
    valid = jnp.arange(W)[None, :] < res.num_new[:, None]
    widx = jnp.where(valid, widx, cap)             # out-of-bounds ⇒ dropped
    out_buf = out_buf.at[jnp.arange(B)[:, None], widx].set(
        res.new_tokens, mode="drop")
    cursor = cursor + res.num_new
    nacc_buf = lax.dynamic_update_slice(
        nacc_buf, res.n_accepted[None, :].astype(nacc_buf.dtype),
        (row_idx, 0))
    nn_buf = lax.dynamic_update_slice(
        nn_buf, res.num_new[None, :].astype(nn_buf.dtype), (row_idx, 0))
    return out_buf, cursor, nacc_buf, nn_buf


@dataclass
class GenerationStats:
    iterations: int = 0
    proposed: int = 0
    accepted: int = 0
    tokens: int = 0
    wall_s: float = 0.0
    prefill_s: float = 0.0           # prompt-processing wall time (≈ TTFT)
    virtual_ms: float = 0.0          # simulated edge-cloud time (incl. RTT)
    acceptance_seqs: list = field(default_factory=list)  # per-seq 0/1 bits
    gamma_seq: list = field(default_factory=list)
    produced: Any = None             # (B,) per-sequence tokens produced
                                     # (anchor included; ≤ max_new; < only
                                     # on EOS stop)
    pipeline_hits: int = 0           # optimistic cross-round windows kept
    pipeline_misses: int = 0         # optimistic windows rolled back

    @property
    def acceptance_rate(self) -> float:
        return self.accepted / max(1, self.proposed)

    @property
    def tokens_per_iteration(self) -> float:
        return self.tokens / max(1, self.iterations)

    @property
    def prefill_ms(self) -> float:
        return self.prefill_s * 1e3


DEFAULT_GAMMA_MAX = 8


class SpecDecodeEngine:
    """Edge draft + cloud target, window policy in the loop.

    ``gamma_max`` pins the compile-time window width: when set, the decode
    step is compiled once at that width and serves every policy and every
    γ ∈ [1, gamma_max] via acceptance masking (policy decisions above it
    are clamped). When ``None`` the width is derived per-generate from the
    policy's own ``gamma_bound()`` — a static-γ workload then compiles at
    exactly its γ. ``sync_every`` sets how many iterations run between host
    synchronizations of the device-resident cursors/stats.
    """

    def __init__(self, draft_cfg: ModelConfig, target_cfg: ModelConfig,
                 draft_params=None, target_params=None,
                 key: Optional[jax.Array] = None,
                 temperature: float = 1.0, rtt_ms: float = 0.0,
                 use_verify_kernel: bool = False,
                 gamma_max: Optional[int] = None, sync_every: int = 8):
        assert draft_cfg.vocab == target_cfg.vocab, \
            "draft/target must share a tokenizer/vocab"
        self.draft_cfg, self.target_cfg = draft_cfg, target_cfg
        self.draft = build_model(draft_cfg)
        self.target = build_model(target_cfg)
        key = key if key is not None else jax.random.PRNGKey(0)
        kd, kt = jax.random.split(key)
        self.draft_params = (draft_params if draft_params is not None
                             else self.draft.init_params(kd))
        self.target_params = (target_params if target_params is not None
                              else self.target.init_params(kt))
        self.temperature = temperature
        self.rtt_ms = rtt_ms
        self.use_verify_kernel = use_verify_kernel
        self.gamma_max = None if gamma_max is None else int(gamma_max)
        self.sync_every = int(sync_every)
        self._target_attention = target_cfg.arch_type in (
            "dense", "moe", "vlm", "encdec")
        self._draft_attention = draft_cfg.arch_type in (
            "dense", "moe", "vlm", "encdec")
        self._jit_cache: dict = {}
        self._split = None

    def split_workers(self):
        """The engine split at the wire: ``(DraftWorker, TargetWorker)``.

        The workers share this engine's models, params and ``_jit_cache``
        (so :meth:`compiled_programs` counts their programs and the
        zero-recompile invariant covers the distributed path). Built
        lazily — colocated sessions never construct them."""
        if self._split is None:
            from ..distributed.workers import DraftWorker, TargetWorker
            self._split = (DraftWorker(self), TargetWorker(self))
        return self._split

    # ------------------------------------------------------------- jit paths

    def _fused_step(self, gamma_max: int):
        """Attention-target path: ONE jitted program at gamma_max; the
        per-iteration γ arrives as the traced ``active_gamma`` scalar and
        the per-slot lifecycle (budget/EOS/done) as traced (B,) buffers.

        Finished/free rows commit nothing and their position freezes; the
        window KV they still write lands in the speculative region
        ``pos..pos+γ`` (beyond their committed prefix, masked out of
        attention by ``pos_map``) and is fully overwritten by the next
        prefill-insert into that slot, so no per-row cache select is
        needed."""
        keyt = ("fused", gamma_max)
        if keyt in self._jit_cache:
            return self._jit_cache[keyt]

        draft_decode = lambda p, t, c, pos: self.draft.decode_step(p, t, c, pos)
        target_verify = lambda p, w, c, pos: self.target.verify_step(p, w, c, pos)

        def step(draft_params, target_params, state, key, active_gamma,
                 row_idx, out_buf, cursor, nacc_buf, nn_buf, max_new, done,
                 eos_id):
            res = spec_decode_step(draft_decode, target_verify,
                                   draft_params, target_params,
                                   state, gamma_max, key, self.temperature,
                                   active_gamma=active_gamma)
            stop = slot_stop_mask(res.num_new, res.n_accepted,
                                  res.new_tokens, cursor, max_new, done,
                                  eos_id)
            new_state = SpecDecodeState(
                draft_cache=res.state.draft_cache,
                target_cache=res.state.target_cache,
                last_token=jnp.where(done, state.last_token,
                                     res.state.last_token),
                pos=state.pos + stop.num_new)
            out = SpecDecodeOut(state=new_state, new_tokens=res.new_tokens,
                                num_new=stop.num_new,
                                n_accepted=stop.n_accepted)
            out_buf, cursor, nacc_buf, nn_buf = _accumulate(
                out, out_buf, cursor, nacc_buf, nn_buf, row_idx)
            return new_state, out_buf, cursor, nacc_buf, nn_buf, stop.done

        jitted = jax.jit(step, donate_argnums=(2, 6, 7, 8, 9, 11))
        self._jit_cache[keyt] = jitted
        return jitted

    def _tree_step(self, d_max: int, b_max: int):
        """Tree-speculation path: ONE jitted program per (d_max, b_max)
        grid bound. The per-round shape — active depth γ ≤ d_max and
        branch count b ≤ b_max — arrives as traced scalars that only mask
        acceptance (``node_valid``), so {γ, b} vary every round with zero
        recompiles, exactly like the linear step's ``active_gamma``.

        Greedy-only (the longest-accepted-root-path rule is the greedy
        accept rule's generalization; stochastic tree acceptance would
        need per-branch residual bookkeeping) and dense/moe-only on both
        sides (the relocation commit is pos_map surgery on a dense
        non-ring cache)."""
        keyt = ("tree", d_max, b_max)
        if keyt in self._jit_cache:
            return self._jit_cache[keyt]
        if self.temperature > 0.0:
            raise NotImplementedError(
                "tree speculation is greedy-only (temperature 0)")
        if not (self._target_attention and self._draft_attention):
            raise NotImplementedError(
                "tree speculation needs attention-family draft and target")
        from .tree import (TreeSpec, tree_committed, tree_path_from_winner,
                           tree_propose, verify_tree_greedy)
        from ..models.kvcache import tree_commit_cache
        spec = TreeSpec(d_max, b_max)
        T = spec.n_entries

        def step(draft_params, target_params, state, key, active_gamma,
                 branches, row_idx, out_buf, cursor, nacc_buf, nn_buf,
                 max_new, done, eos_id):
            tree_tokens, dcache = tree_propose(
                self.draft, draft_params, state.draft_cache,
                state.last_token, state.pos, spec)
            p_logits, tcache = self.target.verify_step(
                target_params, tree_tokens, state.target_cache, state.pos,
                slot_off=jnp.arange(T), pos_off=spec.tree_pos,
                win_mask=spec.win_mask)
            node_valid = spec.node_valid(active_gamma, branches)
            if self.use_verify_kernel:
                from ..kernels.verify.ops import tree_verify_fused
                n_acc, winner, bonus = tree_verify_fused(
                    tree_tokens, p_logits, spec.parent_entry, spec.tree_pos,
                    node_valid, spec.win_mask)
                from .tree import TreeVerifyResult
                res = TreeVerifyResult(
                    n_accepted=n_acc, next_token=bonus, winner=winner,
                    path=tree_path_from_winner(winner, spec.parent_entry,
                                               spec.tree_pos, d_max),
                    accept=jnp.zeros_like(tree_tokens, bool))
            else:
                res = verify_tree_greedy(
                    tree_tokens, p_logits, spec.parent_entry, spec.tree_pos,
                    node_valid, spec.win_mask, d_max)
            new_tokens, num_new = tree_committed(tree_tokens, res, d_max)
            stop = slot_stop_mask(num_new, res.n_accepted, new_tokens,
                                  cursor, max_new, done, eos_id)
            # Relocate the winning path onto canonical slots in BOTH caches
            # (tree slots ≠ positions, so the linear path's implicit
            # stale-masking is not enough here). Lifecycle-clamped counts:
            # tokens beyond the budget/EOS cut are scrubbed, not kept.
            tcache = tree_commit_cache(tcache, state.pos, res.path,
                                       stop.n_accepted, T)
            dcache = tree_commit_cache(dcache, state.pos, res.path,
                                       stop.n_accepted, T)
            new_state = SpecDecodeState(
                draft_cache=dcache, target_cache=tcache,
                last_token=jnp.where(done, state.last_token,
                                     res.next_token),
                pos=state.pos + stop.num_new)
            out = SpecDecodeOut(state=new_state, new_tokens=new_tokens,
                                num_new=stop.num_new,
                                n_accepted=stop.n_accepted)
            out_buf, cursor, nacc_buf, nn_buf = _accumulate(
                out, out_buf, cursor, nacc_buf, nn_buf, row_idx)
            return new_state, out_buf, cursor, nacc_buf, nn_buf, stop.done

        jitted = jax.jit(step, donate_argnums=(2, 7, 8, 9, 10, 12))
        self._jit_cache[keyt] = jitted
        return jitted

    def _split_step(self, gamma_max: int):
        """Path for pairs with a recurrent (SSM/hybrid) side: a recurrent
        side's state cannot be masked retroactively, so it is verified (or
        drafted) on a throwaway copy and then advanced over the committed
        prefix with an active-masked ``lax.scan``. An attention target
        keeps its verify-pass cache instead (``pos_map`` masks the stale
        window tail), as the split TargetWorker does. Per-slot stopping
        composes naturally: the advance is masked by the *stopped*
        ``num_new``, so a finished/free row's recurrent state (and hybrid
        shared-attention cache) never advances."""
        keyt = ("split", gamma_max)
        if keyt in self._jit_cache:
            return self._jit_cache[keyt]

        draft_decode = lambda p, t, c, pos: self.draft.decode_step(p, t, c, pos)

        def step(draft_params, target_params, state, key, active_gamma,
                 row_idx, out_buf, cursor, nacc_buf, nn_buf, max_new, done,
                 eos_id):
            kd, kv = jax.random.split(key)
            prop = draft_propose(draft_decode, draft_params,
                                 state.draft_cache, state.last_token,
                                 state.pos, gamma_max, kd, self.temperature)
            window = jnp.concatenate(
                [state.last_token[:, None], prop.tokens], axis=1)
            p_logits, tcache_spec = self.target.verify_step(
                target_params, window, state.target_cache, state.pos)
            if self.temperature <= 0.0:
                res = verify_window_greedy(prop.tokens, p_logits,
                                           active_gamma=active_gamma)
            else:
                p_probs = _temperature_probs(p_logits, self.temperature)
                res = verify_window(kv, prop.tokens, prop.q_probs, p_probs,
                                    active_gamma=active_gamma)

            arange = jnp.arange(gamma_max + 1)[None, :]
            acc_part = jnp.concatenate(
                [prop.tokens, jnp.zeros_like(prop.tokens[:, :1])], axis=1)
            committed = jnp.where(arange == res.n_accepted[:, None],
                                  res.next_token[:, None], acc_part)
            new_tokens = jnp.where(arange < res.num_new[:, None],
                                   committed, -1)
            stop = slot_stop_mask(res.num_new, res.n_accepted, new_tokens,
                                  cursor, max_new, done, eos_id)

            # advance target over [last_token, committed[:num_new-1]] — i.e.
            # the tokens whose state transitions are now final. committed[t]
            # enters the state only when the *next* window processes it, so
            # we advance exactly num_new tokens starting from last_token.
            adv_tokens = jnp.concatenate(
                [state.last_token[:, None], committed[:, :gamma_max]], axis=1)
            if self._target_attention:
                tcache = tcache_spec
            else:
                tcache = _scan_cache_advance(
                    self.target.decode_step, target_params,
                    state.target_cache, adv_tokens, state.pos, stop.num_new)

            dcache = prop.cache
            if not self._draft_attention:
                # same treatment for a recurrent draft: re-advance from the
                # window-start checkpoint over the committed prefix
                dcache = _scan_cache_advance(
                    self.draft.decode_step, draft_params, state.draft_cache,
                    adv_tokens, state.pos, stop.num_new)

            out = SpecDecodeOut(
                state=SpecDecodeState(
                    draft_cache=dcache, target_cache=tcache,
                    last_token=jnp.where(done, state.last_token,
                                         res.next_token),
                    pos=state.pos + stop.num_new),
                new_tokens=new_tokens, num_new=stop.num_new,
                n_accepted=stop.n_accepted)
            out_buf, cursor, nacc_buf, nn_buf = _accumulate(
                out, out_buf, cursor, nacc_buf, nn_buf, row_idx)
            return out.state, out_buf, cursor, nacc_buf, nn_buf, stop.done

        jitted = jax.jit(step, donate_argnums=(2, 6, 7, 8, 9, 11))
        self._jit_cache[keyt] = jitted
        return jitted

    def _step_fn(self, gamma_max: int):
        if self._target_attention and self._draft_attention:
            return self._fused_step(gamma_max)
        return self._split_step(gamma_max)

    def compiled_programs(self) -> int:
        """Number of distinct XLA step programs compiled so far (the
        compile-once invariant: adaptive-γ generation keeps this at 1)."""
        from ..analysis.sanitize import jit_cache_programs
        return jit_cache_programs(self._jit_cache.values())

    def _policy_gamma_bound(self, policy) -> int:
        """Static window bound to compile the step at: the policy's own
        declared bound when it has one, else the module default."""
        bound = getattr(policy, "gamma_bound", None)
        g = bound() if callable(bound) else DEFAULT_GAMMA_MAX
        return max(1, int(g))

    def _insert_step(self, capacity: int, slots: int, pad_len: int):
        """ONE jitted prefill-insert program per session geometry: prefill a
        single ``pad_len``-padded prompt (true length ``plen`` traced) and
        write its cache row, anchor token, position and lifecycle entries
        into batch row ``slot`` of a LIVE session — neighbouring rows'
        buffers are donated through untouched. ``slot``, ``plen`` and
        ``req_max_new`` are traced, so admission into any slot at any
        prompt length ≤ pad_len reuses the same XLA program."""
        keyt = ("insert", capacity, slots, pad_len)
        if keyt in self._jit_cache:
            return self._jit_cache[keyt]

        def insert(draft_params, target_params, state, out_buf, cursor,
                   max_new_buf, done, prompt, plen, slot, req_max_new, key):
            one = self._prefill(prompt, slots, key, prompt_lens=plen,
                                draft_params=draft_params,
                                target_params=target_params)
            state = insert_slot(state, one, slot)
            row = jnp.full((1, out_buf.shape[1]), -1, jnp.int32)
            row = row.at[0, 0].set(one.last_token[0])
            out_buf = lax.dynamic_update_index_in_dim(out_buf, row, slot, 0)
            cursor = cursor.at[slot].set(1)
            max_new_buf = max_new_buf.at[slot].set(req_max_new)
            done = done.at[slot].set(False)
            return state, out_buf, cursor, max_new_buf, done

        jitted = jax.jit(insert, donate_argnums=(2, 3, 4, 5, 6))
        self._jit_cache[keyt] = jitted
        return jitted

    def _insert_step_paged(self, capacity: int, slots: int, pad_len: int,
                           d_nlog: int, t_nlog: int):
        """Paged-session admission program: prefill one prompt into a DENSE
        batch-1 row (``slots`` = the pool's logical length), then scatter
        that row into the reserved pool blocks and point the slot's block
        table at them (:func:`paged_insert_row`). Non-paged sides (e.g. an
        SSM draft) insert dense as before. ``draft_blocks``/``target_blocks``
        are traced (−1-padded, fixed widths ``d_nlog``/``t_nlog``; width 0
        for an unpaged side), so any slot with any block reservation reuses
        one XLA program — the zero-recompile invariant extends to paged
        admission."""
        keyt = ("insert-paged", capacity, slots, pad_len, d_nlog, t_nlog)
        if keyt in self._jit_cache:
            return self._jit_cache[keyt]

        def insert(draft_params, target_params, state, out_buf, cursor,
                   max_new_buf, done, prompt, plen, slot, req_max_new, key,
                   draft_blocks, target_blocks):
            one = self._prefill(prompt, slots, key, prompt_lens=plen,
                                draft_params=draft_params,
                                target_params=target_params)

            def put(cache, row, blocks):
                if isinstance(cache, PagedAttnCache):
                    return paged_insert_row(cache, row, blocks, slot)
                return insert_slot(cache, row, slot)

            state = SpecDecodeState(
                draft_cache=put(state.draft_cache, one.draft_cache,
                                draft_blocks),
                target_cache=put(state.target_cache, one.target_cache,
                                 target_blocks),
                last_token=state.last_token.at[slot].set(one.last_token[0]),
                pos=state.pos.at[slot].set(one.pos[0]))
            row = jnp.full((1, out_buf.shape[1]), -1, jnp.int32)
            row = row.at[0, 0].set(one.last_token[0])
            out_buf = lax.dynamic_update_index_in_dim(out_buf, row, slot, 0)
            cursor = cursor.at[slot].set(1)
            max_new_buf = max_new_buf.at[slot].set(req_max_new)
            done = done.at[slot].set(False)
            return state, out_buf, cursor, max_new_buf, done

        jitted = jax.jit(insert, donate_argnums=(2, 3, 4, 5, 6))
        self._jit_cache[keyt] = jitted
        return jitted

    def _release_step(self):
        """Retirement program for paged sessions: scrub the slot's block
        table rows to −1 so the frozen slot's ongoing (masked) speculative
        window writes DROP instead of stomping blocks the allocator is
        about to hand to the next request. Runs on the device stream before
        any later insert can reuse the blocks. Dense caches pass through
        untouched (their rows are fully overwritten at the next insert)."""
        keyt = ("release",)
        if keyt in self._jit_cache:
            return self._jit_cache[keyt]

        def release(state, slot):
            def rel(cache):
                if isinstance(cache, PagedAttnCache):
                    return paged_release_slot(cache, slot)
                return cache
            return SpecDecodeState(draft_cache=rel(state.draft_cache),
                                   target_cache=rel(state.target_cache),
                                   last_token=state.last_token,
                                   pos=state.pos)

        jitted = jax.jit(release, donate_argnums=(0,))
        self._jit_cache[keyt] = jitted
        return jitted

    # --------------------------------------------------------------- prefill

    def _prefill(self, prompts: jax.Array, slots: int, key: jax.Array,
                 frontend=None, prompt_lens: Optional[jax.Array] = None,
                 draft_params=None, target_params=None
                 ) -> SpecDecodeState:
        """Right-padded batched prefill. With ``prompt_lens``, the anchor
        logit is gathered at each sequence's true last prompt token; padded
        cache slots are later overwritten before any query can attend them
        (slot j is rewritten by the window covering position j), and SSM
        state is identity-masked past the true length. ``draft_params`` /
        ``target_params`` override the engine's own (so jitted callers can
        pass them as traced arguments instead of baked-in constants)."""
        B, S = prompts.shape
        dp = self.draft_params if draft_params is None else draft_params
        tp = self.target_params if target_params is None else target_params
        dlg, dcache = self.draft.prefill(dp, prompts, slots,
                                         frontend=frontend,
                                         prompt_lens=prompt_lens)
        tlg, tcache = self.target.prefill(tp, prompts, slots,
                                          frontend=frontend,
                                          prompt_lens=prompt_lens)
        if prompt_lens is None:
            anchor = tlg[:, -1, :]
            pos = jnp.full((B,), S, jnp.int32)
        else:
            idx = (prompt_lens - 1)[:, None, None]
            anchor = jnp.take_along_axis(tlg, idx, axis=1)[:, 0, :]
            pos = prompt_lens.astype(jnp.int32)
        if self.temperature <= 0.0:
            first = jnp.argmax(anchor, axis=-1).astype(jnp.int32)
        else:
            probs = _temperature_probs(anchor, self.temperature)
            first = sample_from_probs(key, probs).astype(jnp.int32)
        return SpecDecodeState(draft_cache=dcache, target_cache=tcache,
                               last_token=first, pos=pos)

    # -------------------------------------------------------------- generate

    def generate(self, prompts: np.ndarray, max_new_tokens: int,
                 window_policy: Optional[WindowPolicy] = None,
                 key: Optional[jax.Array] = None, frontend=None,
                 prompt_lens: Optional[np.ndarray] = None,
                 gamma_max: Optional[int] = None,
                 sync_every: Optional[int] = None,
                 eos_id: int = -1, transport=None,
                 mode_policy: str = "auto"
                 ) -> tuple[np.ndarray, GenerationStats]:
        """Batched generation. Returns (tokens (B, max_new), stats).

        This is now a thin ONE-WAVE wrapper over
        :class:`repro.core.session.DecodeSession`: all B prompts are
        admitted together via a batched prefill, the session's masked-γ /
        masked-slot step runs until every row stops (per-row budget, or a
        committed ``eos_id`` ≥ 0), and the device-resident output buffer is
        extracted once. Continuous serving — in-flight admission into freed
        slots — uses the session directly (``repro.serving``). Compile-width
        resolution for ``gamma_max``: this call's override > the
        engine-level pin > the policy's declared bound; policy γ decisions
        above the width are clamped. ``transport``/``mode_policy`` pass
        through to the session: with a transport, every speculation round
        is a real draft→verify→verdict exchange between the split workers
        (:mod:`repro.distributed`).
        """
        from .session import DecodeSession    # session imports engine types
        policy = window_policy or StaticWindowPolicy(4)
        if gamma_max:
            gmax = int(gamma_max)
        elif self.gamma_max:
            gmax = self.gamma_max
        else:
            gmax = self._policy_gamma_bound(policy)
        sync = max(1, int(sync_every if sync_every else self.sync_every))
        B = prompts.shape[0]
        t0 = time.perf_counter()
        sess = DecodeSession(self, capacity=B, max_new_cap=max_new_tokens,
                             gamma_max=gmax, sync_every=sync, eos_id=eos_id,
                             key=key, transport=transport,
                             mode_policy=mode_policy)
        sess.admit_batch(prompts, max_new_tokens, prompt_lens=prompt_lens,
                         frontend=frontend)
        max_iters = max_new_tokens + sync
        while sess.unfinished and sess.iterations < max_iters:
            sess.run_chunk(policy, max_iters=max_iters)
        tokens, stats = sess.snapshot()
        stats.wall_s = time.perf_counter() - t0
        return tokens, stats

    # ------------------------------------------------------------ trace capture

    def capture_traces(self, prompts: np.ndarray, max_new_tokens: int,
                       gamma: int = 8, key=None) -> list[list[int]]:
        """Ground-truth acceptance sequences for DSD-Sim (paper §3.2)."""
        _, stats = self.generate(prompts, max_new_tokens,
                                 StaticWindowPolicy(gamma), key=key)
        return stats.acceptance_seqs
