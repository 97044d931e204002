"""DraftWorker / TargetWorker — the engine's decode step split at the wire.

The colocated :class:`repro.core.engine.SpecDecodeEngine` fuses one
speculation iteration (draft propose → target verify → commit) into a
single XLA program. Distributed execution splits that program at exactly
the points where bytes cross the network:

- :class:`DraftWorker` (edge) owns the draft model and compiles
  ``propose`` (the γ_max-wide autoregressive proposal scan), ``ingest``
  (advance one committed token during fused rounds) and ``advance``
  (recurrent-draft re-advance over the committed prefix).
- :class:`TargetWorker` (cloud) owns the target model and compiles
  ``verify_commit``: window verification, the accept/resample rule,
  per-slot lifecycle masking (:func:`repro.core.specdec.slot_stop_mask`)
  and output-buffer accumulation — byte-for-byte the target half of the
  engine's fused/split step, so a round through
  :class:`repro.distributed.transport.InProcessTransport` commits greedy
  tokens bit-identical to the colocated path.

Both workers register their jitted programs in the owning engine's
``_jit_cache`` so ``engine.compiled_programs()`` keeps counting every XLA
program and the session's zero-recompile invariant extends to the
distributed path (γ and the slot lifecycle stay traced).

The workers do not donate their cache operands: the draft's pre-window
cache doubles as the recurrent-family rollback checkpoint, and the
round-trip through the transport keeps a host sync per iteration anyway —
simplicity wins over the colocated path's in-place-update optimization
here. Cross-round pipelining leans on exactly this: the session's
optimistic draft of window k+1 reuses ``advance`` (recurrent drafts: the
same re-advance program runs once under the all-accept assumption and
again from the kept checkpoint on a rollback) and the undonated
``propose`` output (attention drafts: the pre-speculation propose cache
IS the rollback state — the speculative window's extra KV writes live
only in the discarded cache), so hits, rollbacks and mode switches add
zero XLA programs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.engine import _accumulate, _scan_cache_advance, _tree_where
from ..core.specdec import (SpecDecodeOut, _temperature_probs, draft_propose,
                            slot_stop_mask, verify_window,
                            verify_window_greedy)
from ..core.tree import (TreeSpec, tree_committed, tree_propose,
                         verify_tree_greedy)
from ..models.kvcache import tree_commit_cache


class DraftWorker:
    """Edge-side worker: proposes speculation windows, tracks the committed
    prefix through verdicts."""

    def __init__(self, engine):
        self.engine = engine
        self.model = engine.draft
        self.params = engine.draft_params
        self.attention = engine._draft_attention
        self.temperature = engine.temperature

    # -- jitted programs ----------------------------------------------------

    def propose(self, gamma_max: int):
        """(params, cache, last_token, pos, key) → (tokens, q_probs, cache).

        Always scans the full ``gamma_max`` window (the compile-once
        invariant); the active γ of the round only masks acceptance on the
        target side and prices the wire payload."""
        keyt = ("dw_propose", gamma_max)
        cache = self.engine._jit_cache
        if keyt in cache:
            return cache[keyt]
        decode = lambda p, t, c, pos: self.model.decode_step(p, t, c, pos)

        def propose(params, dcache, last_token, pos, key):
            prop = draft_propose(decode, params, dcache, last_token, pos,
                                 gamma_max, key, self.temperature)
            return prop.tokens, prop.q_probs, prop.cache

        cache[keyt] = jax.jit(propose)
        return cache[keyt]

    def propose_tree(self, d_max: int, b_max: int):
        """(params, cache, last_token, pos) → (tree_tokens (B, T), cache).

        Greedy grid-tree proposal (:func:`repro.core.tree.tree_propose`):
        one anchor decode + ``d_max − 1`` lockstep frontier passes, always
        the full (d_max, b_max) grid — the round's (γ, b) only masks
        acceptance target-side, like the linear propose always scanning
        γ_max. Attention drafts only (tree slots need a KV pos_map)."""
        keyt = ("dw_propose_tree", d_max, b_max)
        cache = self.engine._jit_cache
        if keyt in cache:
            return cache[keyt]
        assert self.attention, \
            "tree speculation needs an attention-family draft"
        spec = TreeSpec(d_max, b_max)

        def propose_tree(params, dcache, last_token, pos):
            return tree_propose(self.model, params, dcache, last_token,
                                pos, spec)

        cache[keyt] = jax.jit(propose_tree)
        return cache[keyt]

    def ingest_tree(self, d_max: int, b_max: int):
        """(propose_cache, pos, path, n_accepted) → cache.

        Verdict application for tree rounds: relocate the winning path's
        KV from grid slots onto the canonical linear slots and scrub the
        losing branches — the draft-side mirror of the target's tree
        commit, so both caches agree on the committed prefix layout."""
        keyt = ("dw_ingest_tree", d_max, b_max)
        cache = self.engine._jit_cache
        if keyt in cache:
            return cache[keyt]
        assert self.attention, \
            "tree speculation needs an attention-family draft"
        n_entries = 1 + d_max * b_max

        def ingest_tree(dcache, pos, path, n_accepted):
            return tree_commit_cache(dcache, pos, path, n_accepted,
                                     n_entries)

        cache[keyt] = jax.jit(ingest_tree)
        return cache[keyt]

    def ingest(self):
        """(params, cache, token, pos, num_new) → cache.

        Fused rounds produce one target token per iteration without a
        draft window; the draft still ingests the previous anchor token at
        its position so its cache tracks the committed prefix and a later
        switch back to distributed mode proposes from a coherent state.
        Rows with ``num_new == 0`` (done/free) keep their old cache."""
        keyt = ("dw_ingest",)
        cache = self.engine._jit_cache
        if keyt in cache:
            return cache[keyt]

        def ingest(params, dcache, token, pos, num_new):
            _, cnew = self.model.decode_step(params, token, dcache, pos)
            return _tree_where(num_new > 0, cnew, dcache)

        cache[keyt] = jax.jit(ingest)
        return cache[keyt]

    def advance(self, gamma_max: int):
        """(params, checkpoint_cache, adv_tokens, pos, num_new) → cache.

        Recurrent-draft verdict application: re-advance the pre-window
        cache checkpoint over the committed prefix (the SSM analogue of
        attention's pos_map rollback — same scan the colocated split step
        runs)."""
        keyt = ("dw_advance", gamma_max)
        cache = self.engine._jit_cache
        if keyt in cache:
            return cache[keyt]
        decode = lambda p, t, c, pos: self.model.decode_step(p, t, c, pos)

        def advance(params, dcache, adv_tokens, pos, num_new):
            return _scan_cache_advance(decode, params, dcache, adv_tokens,
                                       pos, num_new)

        cache[keyt] = jax.jit(advance)
        return cache[keyt]


class TargetWorker:
    """Cloud-side worker: verifies windows, owns the committed-token
    buffers and the per-slot lifecycle (budget/EOS enforcement lives where
    the tokens are produced)."""

    def __init__(self, engine):
        self.engine = engine
        self.model = engine.target
        self.params = engine.target_params
        self.attention = engine._target_attention
        self.temperature = engine.temperature

    def verify_commit(self, gamma_max: int):
        """One jitted verdict program at the static window bound.

        Signature (``q_probs`` present only at temperature > 0)::

            (params, tcache, window, pos, active_gamma, key, [q_probs,]
             out_buf, cursor, nacc_buf, nn_buf, max_new, done, row_idx,
             eos_id)
            → (tcache, pos, last_token, out_buf, cursor, nacc_buf, nn_buf,
               done, num_new, n_accepted, next_token_raw)

        ``window`` is ``[last_token, draft_tokens]`` (γ_max+1 wide);
        ``active_gamma`` masks acceptance exactly as in the colocated step
        — γ = 0 is the fused round: nothing accepted, the target's own
        next token at position 0 is committed, no draft required.
        Attention targets keep the speculative window writes (pos_map
        masks the stale tail); SSM/hybrid targets verify on a throwaway
        cache and re-advance the committed prefix with the same masked
        scan the colocated split step uses."""
        keyt = ("tw_verify", gamma_max)
        cache = self.engine._jit_cache
        if keyt in cache:
            return cache[keyt]
        greedy = self.temperature <= 0.0

        def commit(params, tcache, window, pos, active_gamma, key, q_probs,
                   out_buf, cursor, nacc_buf, nn_buf, max_new, done, row_idx,
                   eos_id):
            draft_tokens = window[:, 1:]
            p_logits, tcache_spec = self.model.verify_step(
                params, window, tcache, pos)
            if greedy:
                res = verify_window_greedy(draft_tokens, p_logits,
                                           active_gamma=active_gamma)
            else:
                p_probs = _temperature_probs(p_logits, self.temperature)
                res = verify_window(key, draft_tokens, q_probs, p_probs,
                                    active_gamma=active_gamma)

            arange = jnp.arange(gamma_max + 1)[None, :]
            acc_part = jnp.concatenate(
                [draft_tokens, jnp.zeros_like(draft_tokens[:, :1])], axis=1)
            committed = jnp.where(arange == res.n_accepted[:, None],
                                  res.next_token[:, None], acc_part)
            new_tokens = jnp.where(arange < res.num_new[:, None],
                                   committed, -1)
            stop = slot_stop_mask(res.num_new, res.n_accepted, new_tokens,
                                  cursor, max_new, done, eos_id)

            if self.attention:
                tcache_new = tcache_spec
            else:
                adv_tokens = jnp.concatenate(
                    [window[:, :1], committed[:, :gamma_max]], axis=1)
                tcache_new = _scan_cache_advance(
                    self.model.decode_step, params, tcache, adv_tokens,
                    pos, stop.num_new)

            out = SpecDecodeOut(state=None, new_tokens=new_tokens,
                                num_new=stop.num_new,
                                n_accepted=stop.n_accepted)
            out_buf, cursor, nacc_buf, nn_buf = _accumulate(
                out, out_buf, cursor, nacc_buf, nn_buf, row_idx)
            last = jnp.where(done, window[:, 0], res.next_token)
            return (tcache_new, pos + stop.num_new, last, out_buf, cursor,
                    nacc_buf, nn_buf, stop.done, stop.num_new,
                    stop.n_accepted, res.next_token)

        # the jitted function's name is the program's name in the trace
        if greedy:
            def verify_commit(params, tcache, window, pos, active_gamma, key,
                              out_buf, cursor, nacc_buf, nn_buf, max_new,
                              done, row_idx, eos_id):
                return commit(params, tcache, window, pos, active_gamma, key,
                              None, out_buf, cursor, nacc_buf, nn_buf,
                              max_new, done, row_idx, eos_id)
        else:
            def verify_commit(params, tcache, window, pos, active_gamma, key,
                              q_probs, out_buf, cursor, nacc_buf, nn_buf,
                              max_new, done, row_idx, eos_id):
                return commit(params, tcache, window, pos, active_gamma, key,
                              q_probs, out_buf, cursor, nacc_buf, nn_buf,
                              max_new, done, row_idx, eos_id)
        cache[keyt] = jax.jit(verify_commit)
        return cache[keyt]

    def verify_commit_tree(self, d_max: int, b_max: int):
        """The tree-round verdict program (greedy only).

        Signature::

            (params, tcache, tree_tokens, pos, active_gamma, branches,
             out_buf, cursor, nacc_buf, nn_buf, max_new, done, row_idx,
             eos_id)
            → (tcache, pos, last_token, out_buf, cursor, nacc_buf, nn_buf,
               done, num_new, n_accepted, next_token_raw, path)

        ``tree_tokens`` is the (B, T) grid window (entry 0 = anchor); one
        ancestor-masked verify pass scores every entry, the longest-
        accepted-root-path rule picks the winner, and
        :func:`repro.models.kvcache.tree_commit_cache` relocates the
        winning path onto the canonical linear slots. The extra ``path``
        output lets the draft side run the same relocation on its propose
        cache (:meth:`DraftWorker.ingest_tree`). Attention targets only —
        the grid writes slots ≠ positions, which needs a pos_map."""
        keyt = ("tw_verify_tree", d_max, b_max)
        cache = self.engine._jit_cache
        if keyt in cache:
            return cache[keyt]
        assert self.temperature <= 0.0, \
            "tree speculation is greedy-only (no per-branch q dists yet)"
        assert self.attention, \
            "tree speculation needs an attention-family target"
        spec = TreeSpec(d_max, b_max)
        T = spec.n_entries

        def verify_commit_tree(params, tcache, tree_tokens, pos,
                               active_gamma, branches, out_buf, cursor,
                               nacc_buf, nn_buf, max_new, done, row_idx,
                               eos_id):
            p_logits, tcache_spec = self.model.verify_step(
                params, tree_tokens, tcache, pos,
                slot_off=jnp.arange(T, dtype=jnp.int32),
                pos_off=spec.tree_pos, win_mask=spec.win_mask)
            node_valid = spec.node_valid(active_gamma, branches)
            res = verify_tree_greedy(tree_tokens, p_logits,
                                     spec.parent_entry, spec.tree_pos,
                                     node_valid, spec.win_mask, d_max)
            new_tokens, num_new = tree_committed(tree_tokens, res, d_max)
            stop = slot_stop_mask(num_new, res.n_accepted, new_tokens,
                                  cursor, max_new, done, eos_id)
            tcache_new = tree_commit_cache(tcache_spec, pos, res.path,
                                           stop.n_accepted, T)
            out = SpecDecodeOut(state=None, new_tokens=new_tokens,
                                num_new=stop.num_new,
                                n_accepted=stop.n_accepted)
            out_buf, cursor, nacc_buf, nn_buf = _accumulate(
                out, out_buf, cursor, nacc_buf, nn_buf, row_idx)
            last = jnp.where(done, tree_tokens[:, 0], res.next_token)
            return (tcache_new, pos + stop.num_new, last, out_buf, cursor,
                    nacc_buf, nn_buf, stop.done, stop.num_new,
                    stop.n_accepted, res.next_token, res.path)

        cache[keyt] = jax.jit(verify_commit_tree)
        return cache[keyt]
