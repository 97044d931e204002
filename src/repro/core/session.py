"""Persistent slot-based decode session — continuous batching on real models.

:class:`DecodeSession` owns a fixed-capacity pool of batch rows ("slots")
whose KV/SSM caches, output buffers and cursors live ON DEVICE across
requests. Requests are admitted into free slots by a jitted prefill-insert
(one program per session geometry, any slot / any prompt length ≤ the pad
bound) and retired from finished slots at ``sync_every`` boundaries; the
engine's compile-once masked-γ step keeps running untouched while the
active-slot pattern changes — admission and retirement are *data*, never a
new XLA program.

Lifecycle of one slot::

    admit (prefill-insert row j)  →  decode chunks (slot active)
        →  done (budget / EOS; num_new masked to 0, row freezes)
        →  retire (tokens extracted, host record closed, slot free)
        →  admit next request (row j fully overwritten)

Invariants the tests pin down:

- a request decoded with staggered co-tenants commits the SAME greedy
  tokens as a solo :meth:`SpecDecodeEngine.generate` run (attention *and*
  SSM/hybrid families) — per-row independence of the masked step;
- retire → re-admit leaves no stale cache state (the insert overwrites the
  whole row; :func:`repro.models.kvcache.reset_slot` additionally scrubs it
  for long-lived sessions);
- the number of compiled XLA programs is constant across any
  admission/retirement pattern after warmup (one step + one insert).

``SpecDecodeEngine.generate`` is a thin one-wave wrapper over this class;
the continuous scheduler in :mod:`repro.serving.server` drives it with a
live arrival queue.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..models.kvcache import BlockAllocator, logical_blocks, reset_slot
# fused-mode tokens stream edge-ward one control round trip per this many
# committed tokens — the same amortization DSD-Sim's ``fused_chunk``
# charges (one shared constant so sim and real paths cannot drift)
from ..sim.network import DEFAULT_FUSED_CHUNK as FUSED_FLUSH_TOKENS
from ..tracing import span
from .engine import DEFAULT_GAMMA_MAX, GenerationStats
from .specdec import SpecDecodeState
from .window import FeatureSnapshot


@dataclass
class SlotRecord:
    """Host-side bookkeeping for the request occupying one slot."""
    request_id: int
    max_new: int
    admit_it: int                    # session iteration at admission
    bits: list = field(default_factory=list)   # acceptance 0/1 stream
    produced: int = 1                # tokens in out_buf row (anchor incl.)
    proposed: int = 0
    accepted: int = 0
    done: bool = False


def _canon(tree):
    """Array-ify non-array leaves (the caches' static ``ring`` flag) so the
    first jitted call sees the same signature the step returns."""
    return jax.tree.map(
        lambda x: x if isinstance(x, jax.Array) else jnp.asarray(x), tree)


class DecodeSession:
    """Fixed-capacity slot pool over a :class:`SpecDecodeEngine`.

    ``capacity``       batch rows (the compiled batch size),
    ``max_new_cap``    output-buffer width (per-request budgets clamp to it),
    ``max_prompt_len`` pad bound for per-slot admission (``admit``); a
                       session only ever driven by ``admit_batch`` may leave
                       it None and inherits the wave's prompt width,
    ``gamma_max``      compile-once window bound (session > engine > default),
    ``sync_every``     decode iterations between host syncs — the admission/
                       retirement granularity,
    ``eos_id``         stop token (−1 disables; per-slot budgets always cap),
    ``transport``      a :class:`repro.distributed.Transport`: when set,
                       speculation rounds run as real draft→verify→verdict
                       exchanges between the engine's DraftWorker and
                       TargetWorker over this transport (colocated fused
                       step otherwise),
    ``mode_policy``    ``"auto"`` honors ``WindowDecision.mode``,
                       ``"distributed"``/``"fused"`` force one mode,
                       ``"pipeline"`` honors the decision like ``auto`` but
                       overlaps rounds: while the target verifies window k
                       the draft optimistically drafts window k+1 from its
                       own proposed continuation, rolling back on partial
                       accepts (requires a transport; γ is capped at
                       ``gamma_max − 1`` because one proposal slot is
                       reserved as the bonus-token guess the next window
                       anchors on),
    ``paged``          attention-family sides store KV in a paged block
                       pool (:class:`repro.models.kvcache.PagedAttnCache`)
                       instead of dense per-slot rows: admission reserves
                       only the blocks the request's ``prompt + budget +
                       2γ`` footprint needs and retirement frees them, so
                       pool bytes bound ADMITTED WORK, not
                       capacity × worst-case length. Greedy committed
                       tokens are bit-identical to the dense layout
                       (``kv_quantize=False``),
    ``kv_block_size``  positions per pool block,
    ``kv_pool_blocks`` physical blocks per pool (int, or
                       ``{"draft": n, "target": m}``); ``None`` sizes the
                       pool at full dense parity — no memory saving, used
                       by the bit-identity tests,
    ``kv_quantize``    int8 per-entry K/V with f32 scales (≈4× fewer pool
                       bytes, approximate attention — see README
                       “Memory & capacity”),
    ``max_branches``   opt-in to TREE speculation: > 0 compiles the
                       (γ_max, max_branches) grid-tree step and lets the
                       window policy pick a per-round branch width b ≤
                       the bound (``WindowDecision.branches``); 0 (the
                       default) keeps the linear chain path untouched.
                       ``max_branches=1`` is the degenerate tree — same
                       committed greedy tokens as the linear path.
                       Greedy-only, attention-family both sides, dense
                       KV, and mutually exclusive with pipeline mode.
    """

    def __init__(self, engine, capacity: int, max_new_cap: int,
                 max_prompt_len: Optional[int] = None,
                 gamma_max: Optional[int] = None,
                 sync_every: Optional[int] = None,
                 eos_id: int = -1, key: Optional[jax.Array] = None,
                 log_gamma: bool = True, transport=None,
                 mode_policy: str = "auto", pair_key: str = "engine",
                 paged: bool = False, kv_block_size: int = 16,
                 kv_pool_blocks: Optional[int] = None,
                 kv_quantize: bool = False,
                 max_branches: int = 0):
        self.engine = engine
        self.capacity = int(capacity)
        self.max_new_cap = int(max_new_cap)
        self.max_prompt_len = (None if max_prompt_len is None
                               else int(max_prompt_len))
        if gamma_max:
            self.gamma_max = int(gamma_max)
        elif engine.gamma_max:
            self.gamma_max = engine.gamma_max
        else:
            self.gamma_max = DEFAULT_GAMMA_MAX
        self.sync_every = max(1, int(sync_every or engine.sync_every))
        self.eos_id = -1 if eos_id is None else int(eos_id)
        self._key = key if key is not None else jax.random.PRNGKey(0)
        assert mode_policy in ("auto", "distributed", "fused",
                               "pipeline"), mode_policy
        if mode_policy == "pipeline":
            assert transport is not None, \
                "pipeline mode overlaps rounds across a transport; " \
                "colocated sessions have nothing to overlap"
            assert self.gamma_max >= 2, \
                "pipeline mode reserves one proposal slot as the bonus " \
                "guess; gamma_max must be ≥ 2"
        self.transport = transport
        self.mode_policy = mode_policy
        # the key this session presents to the window policy: adaptive
        # policies (Dynamic, AWC) keep per-key state, so a multi-pair
        # deployment sharing one policy object still gets one stabilizer
        # per draft–target pair
        self.pair_key = str(pair_key)

        # ---- tree speculation (core/tree.py) ----------------------------
        self.max_branches = int(max_branches or 0)
        self._tree_spec = None
        self._branches_eff = 1
        self._branches_prev = 1.0
        if self.max_branches:
            if mode_policy == "pipeline":
                raise ValueError(
                    "tree speculation does not compose with pipeline mode "
                    "(one in-flight window shape per exchange)")
            if engine.temperature > 0.0:
                raise ValueError("tree speculation is greedy-only "
                                 "(temperature 0)")
            if not (engine._draft_attention and engine._target_attention):
                raise ValueError("tree speculation needs attention-family "
                                 "draft and target")
            if paged:
                raise ValueError(
                    "tree speculation needs dense KV slots (the winning-"
                    "path relocation is pos_map surgery on dense rows)")
            from .tree import TreeSpec
            self._tree_spec = TreeSpec(self.gamma_max, self.max_branches)

        # ---- paged KV slot pool (models/kvcache.PagedAttnCache) ---------
        self.paged = bool(paged)
        self.kv_block_size = int(kv_block_size)
        self.kv_pool_blocks = kv_pool_blocks
        self.kv_quantize = bool(kv_quantize)
        self._paged_sides = {
            "draft": engine.draft_cfg.arch_type in ("dense", "moe"),
            "target": engine.target_cfg.arch_type in ("dense", "moe")}
        if self.paged:
            assert any(self._paged_sides.values()), \
                "paged sessions need at least one attention-family side " \
                "(recurrent state has no positions to page)"
        self._alloc: dict[str, Optional[BlockAllocator]] = {
            "draft": None, "target": None}
        self._slot_blocks: list[Optional[dict]] = [None] * self.capacity

        self.slots_len = (None if self.max_prompt_len is None
                          else self._cache_len(self.max_prompt_len))
        self._state: Optional[SpecDecodeState] = None
        self._slots: list[Optional[SlotRecord]] = [None] * self.capacity
        self._out_buf = None
        self._cursor = None
        self._max_new = None
        self._done = None
        self._nacc = None
        self._nn = None

        # engine-wide accounting / window-policy features. Feature lists
        # are bounded (only the last 16 samples feed FeatureSnapshot) and
        # gamma_seq logging is optional so a long-lived serving session
        # does not grow host state linearly in decode iterations.
        self.iterations = 0
        self.proposed = 0
        self.accepted = 0
        self.prefill_s = 0.0
        self.decode_wall_s = 0.0
        self.virtual_ms = 0.0
        self.log_gamma = bool(log_gamma)
        self.gamma_seq: list[int] = []
        self.gamma_sum = 0           # Σ effective γ over distributed rounds
        self.gamma_rounds = 0        # distributed rounds decided (O(1) mean
                                     # γ even with log_gamma off)
        self.fused_iterations = 0
        self.link_ms = 0.0               # unhidden transport delay so far
        self.pipeline_hits = 0           # optimistic windows kept
        self.pipeline_misses = 0         # optimistic windows rolled back
        self._fused_pending = 0          # fused tokens since last flush
        self._q_zero = None              # cached fused-round q placeholder
        self._alpha_recent: list[float] = []
        self._tpot_recent: list[float] = []
        self._pipe_recent: list[float] = []
        self._round_seq = 0              # wire round ids (RTT pairing)
        self._gamma_prev = 4.0

    # ------------------------------------------------------------- geometry

    def _cache_len(self, prompt_len: int) -> int:
        # 2× the window bound: a pipelined round's optimistic propose can
        # write up to gamma_max positions beyond the half-duplex high-water
        # mark. Applied to every mode so sessions that differ only in
        # mode_policy share one cache geometry (state-comparison tests and
        # jit keys line up; pos_map masking makes the headroom free).
        if self.max_branches:
            # tree rounds write the full (γ_max, b_max) grid past the
            # high-water mark: anchor + γ_max·b_max entries at slots
            # pos .. pos+T−1 (no pipelining, so the 2γ overhang shrinks
            # to γ + grid)
            return (prompt_len + self.max_new_cap + self.gamma_max
                    + self._tree_spec.n_entries + 18)
        return prompt_len + self.max_new_cap + 2 * self.gamma_max + 18

    def _n_logical(self) -> int:
        """Block-table width: logical blocks covering one slot's length."""
        return logical_blocks(self.slots_len, self.kv_block_size)

    def blocks_needed(self, prompt_len: int, max_new: int) -> int:
        """Blocks one request must reserve on each paged side: its prompt
        + clamped budget + speculative-window overhang (2γ covers the
        pipelined optimistic window; +2 the correction/bonus tokens).
        Writes past the reservation are stale speculation by construction
        and DROP harmlessly (models/kvcache.py)."""
        need = min(self.slots_len,
                   int(prompt_len) + min(int(max_new), self.max_new_cap)
                   + 2 * self.gamma_max + 2)
        return logical_blocks(need, self.kv_block_size)

    def _pool_blocks(self, side: str) -> int:
        n = self.kv_pool_blocks
        if isinstance(n, dict):
            n = n.get(side)
        # default: full dense parity (capacity × per-slot blocks) — no
        # memory saving, but functionally identical; benches size it down
        return int(n) if n else self.capacity * self._n_logical()

    def free_kv_blocks(self) -> Optional[int]:
        """Min free blocks across paged sides (None for dense sessions)."""
        if not self.paged:
            return None
        self._ensure_state()
        return min(a.free_blocks for a in self._alloc.values()
                   if a is not None)

    def can_admit(self, prompt_len: int, max_new: int) -> bool:
        """True when a free slot AND (paged) every side's reservation fits.
        The block-aware admission predicate serving uses instead of plain
        free-slot counting."""
        if not self.free:
            return False
        if not self.paged:
            return True
        self._ensure_state()
        need = self.blocks_needed(prompt_len, max_new)
        return all(a is None or a.free_blocks >= need
                   for a in self._alloc.values())

    def _init_buffers(self) -> None:
        B = self.capacity
        self._out_buf = jnp.full((B, self.max_new_cap), -1, jnp.int32)
        self._cursor = jnp.zeros((B,), jnp.int32)
        self._max_new = jnp.zeros((B,), jnp.int32)
        self._done = jnp.ones((B,), bool)          # free slots are inert
        self._nacc = jnp.zeros((self.sync_every, B), jnp.int32)
        self._nn = jnp.zeros((self.sync_every, B), jnp.int32)

    def _ensure_state(self) -> None:
        """Lazily build an all-free device state for per-slot admission."""
        if self._state is not None:
            return
        eng = self.engine
        assert self.max_prompt_len is not None, \
            "per-slot admission needs max_prompt_len at session creation"
        for cfg in (eng.draft_cfg, eng.target_cfg):
            assert cfg.arch_type not in ("vlm", "encdec"), \
                "per-slot admission needs a frontend-free arch; use " \
                "admit_batch for vlm/encdec waves"
        def make_cache(model, side):
            if self.paged and self._paged_sides[side]:
                n_blocks = self._pool_blocks(side)
                self._alloc[side] = BlockAllocator(n_blocks)
                return model.init_paged_cache(
                    self.capacity, self.slots_len, n_blocks,
                    self.kv_block_size, quantize=self.kv_quantize)
            return model.init_cache(self.capacity, self.slots_len)

        self._state = _canon(SpecDecodeState(
            draft_cache=make_cache(eng.draft, "draft"),
            target_cache=make_cache(eng.target, "target"),
            last_token=jnp.zeros((self.capacity,), jnp.int32),
            pos=jnp.zeros((self.capacity,), jnp.int32)))
        self._init_buffers()

    # ------------------------------------------------------------ occupancy

    @property
    def occupied(self) -> list[int]:
        return [j for j, r in enumerate(self._slots) if r is not None]

    @property
    def free(self) -> list[int]:
        return [j for j, r in enumerate(self._slots) if r is None]

    @property
    def unfinished(self) -> bool:
        return any(r is not None and not r.done for r in self._slots)

    def finished_slots(self) -> list[int]:
        return [j for j, r in enumerate(self._slots)
                if r is not None and r.done]

    def record(self, slot: int) -> Optional[SlotRecord]:
        return self._slots[slot]

    @property
    def mean_gamma(self) -> float:
        """Mean effective γ over distributed rounds — O(1) accumulators,
        so it is available even with ``log_gamma`` off (serving sessions)."""
        return (self.gamma_sum / self.gamma_rounds if self.gamma_rounds
                else 0.0)

    # ------------------------------------------------------------- admission

    def admit_batch(self, prompts: np.ndarray, max_new,
                    prompt_lens: Optional[np.ndarray] = None,
                    frontend=None,
                    request_ids: Optional[Sequence[int]] = None) -> list[int]:
        """Admit one full wave into a FRESH session via batched prefill.

        This is the ``generate()`` path (and the only admission path for
        frontend archs). ``max_new`` may be a scalar or a per-slot vector.
        """
        assert self._state is None and not self.occupied, \
            "admit_batch only fills a fresh session; use admit() for " \
            "in-flight admission"
        assert not self.paged, \
            "paged sessions admit per-slot (block reservations are " \
            "per-request); use admit()"
        prompts = jnp.asarray(prompts, jnp.int32)
        B, S = prompts.shape
        assert B == self.capacity, (B, self.capacity)
        if self.max_prompt_len is not None:
            assert S <= self.max_prompt_len, (S, self.max_prompt_len)
            if S < self.max_prompt_len:
                if prompt_lens is None:
                    prompt_lens = np.full((B,), S, np.int32)
                prompts = jnp.pad(prompts,
                                  ((0, 0), (0, self.max_prompt_len - S)))
        else:
            self.slots_len = self._cache_len(S)

        t0 = time.perf_counter()
        self._key, kp = jax.random.split(self._key)
        pl = (None if prompt_lens is None
              else jnp.asarray(prompt_lens, jnp.int32))
        state = self.engine._prefill(prompts, self.slots_len, kp,
                                     frontend=frontend, prompt_lens=pl)
        state = _canon(state)
        self._init_buffers()
        mn = np.minimum(np.broadcast_to(np.asarray(max_new), (B,)),
                        self.max_new_cap).astype(np.int32)
        self._max_new = jnp.asarray(mn)
        self._done = jnp.zeros((B,), bool)
        self._cursor = jnp.ones((B,), jnp.int32)
        self._out_buf = self._out_buf.at[:, 0].set(state.last_token)
        self._state = jax.block_until_ready(state)
        self.prefill_s = time.perf_counter() - t0
        ids = list(request_ids) if request_ids is not None else list(range(B))
        self._slots = [SlotRecord(request_id=ids[j], max_new=int(mn[j]),
                                  admit_it=self.iterations)
                       for j in range(B)]
        return list(range(B))

    def admit(self, prompt: np.ndarray, max_new: int, request_id: int = 0,
              slot: Optional[int] = None, block: bool = True,
              arrival_s: Optional[float] = None) -> int:
        """Admit one request into a free slot of a LIVE session.

        Runs the jitted prefill-insert: the prompt (right-padded to
        ``max_prompt_len``) is prefilled at batch size 1 and its cache row,
        anchor token and lifecycle entries are scattered into the chosen
        slot. The request's first token exists when this returns (with
        ``block=True``) — per-request TTFT is measured from its own
        prefill-insert, not from any wave's. ``arrival_s`` (the request's
        due time on the serve loop's clock) labels the ``dsd.admit``
        span."""
        free = self.free
        if not free:
            raise RuntimeError("no free slot; retire a finished request first")
        j = free[0] if slot is None else slot
        assert self._slots[j] is None, f"slot {j} is occupied"
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        meta = {} if arrival_s is None else {"arrival_s": float(arrival_s)}
        with span("admit", request_id=request_id, prompt_len=prompt.size,
                  **meta):
            budget = self._insert(prompt, max_new, j, block)
        self._slots[j] = SlotRecord(request_id=request_id, max_new=budget,
                                    admit_it=self.iterations)
        return j

    def _insert(self, prompt: np.ndarray, max_new: int, j: int,
                block: bool) -> int:
        """The prefill-insert of :meth:`admit` into slot ``j``; returns
        the request's clamped budget."""
        self._ensure_state()
        P = self.max_prompt_len
        assert 1 <= prompt.size <= P, (prompt.size, P)
        padded = np.zeros((1, P), np.int32)
        padded[0, :prompt.size] = prompt
        budget = min(int(max_new), self.max_new_cap)
        with span("admit.dispatch"):
            self._key, kk = jax.random.split(self._key)
            args = (self.engine.draft_params, self.engine.target_params,
                    self._state, self._out_buf, self._cursor, self._max_new,
                    self._done, jnp.asarray(padded),
                    jnp.asarray([prompt.size], jnp.int32),
                    jnp.asarray(j, jnp.int32), jnp.asarray(budget, jnp.int32),
                    kk)
            if self.paged:
                blocks = self._reserve_blocks(prompt.size, budget)
                insert = self.engine._insert_step_paged(
                    self.capacity, self.slots_len, P,
                    blocks["draft"].shape[0], blocks["target"].shape[0])
                (self._state, self._out_buf, self._cursor, self._max_new,
                 self._done) = insert(*args, jnp.asarray(blocks["draft"]),
                                      jnp.asarray(blocks["target"]))
                self._slot_blocks[j] = {
                    s: [int(i) for i in ids if i >= 0]
                    for s, ids in blocks.items() if ids.size}
            else:
                insert = self.engine._insert_step(self.capacity,
                                                  self.slots_len, P)
                (self._state, self._out_buf, self._cursor, self._max_new,
                 self._done) = insert(*args)
        if block:
            with span("admit.wait"):
                jax.block_until_ready(self._cursor)
        return budget

    def _reserve_blocks(self, prompt_len: int, budget: int
                        ) -> dict[str, np.ndarray]:
        """Reserve each paged side's blocks for one admission, all-or-
        nothing (checks both sides before allocating either, so a shortfall
        never leaks a half-reservation). Returns per-side block-id rows
        padded to the full table width with −1 (unreserved tail)."""
        need = self.blocks_needed(prompt_len, budget)
        n_log = self._n_logical()
        for side, a in self._alloc.items():
            if a is not None and a.free_blocks < need:
                raise RuntimeError(
                    f"insufficient free KV blocks on {side}: need {need}, "
                    f"{a.free_blocks} free of {a.n_blocks} — retire "
                    f"finished requests or grow kv_pool_blocks")
        out = {}
        for side, a in self._alloc.items():
            if a is None:
                out[side] = np.zeros((0,), np.int32)
                continue
            row = np.full((n_log,), -1, np.int32)
            row[:need] = a.alloc(need)
            out[side] = row
        return out

    # -------------------------------------------------------------- decode

    def _decide(self, policy, q_depth: float) -> tuple[int, bool]:
        """One window-policy decision → (effective γ, fused?).

        ``mode_policy`` overrides the decision's mode; a fused round runs
        with effective γ = 0 — the traced ``active_gamma`` masks the whole
        window, so nothing is accepted and the target's own next token is
        committed (a pure cloud-side autoregressive step). γ = 0 is data,
        not shape: fused/distributed switching never recompiles."""
        with span("decide"):
            dec = policy.decide(self.pair_key, self._features(q_depth))
        if self.mode_policy == "fused":
            fused = True
        elif self.mode_policy == "distributed":
            fused = False
        else:                     # auto and pipeline honor the decision
            fused = dec.mode == "fused"
        # pipeline mode reserves the (γ+1)-th proposal as the bonus guess
        # the optimistic next window anchors on, so γ caps one below the
        # compiled width
        cap = (self.gamma_max - 1 if self.mode_policy == "pipeline"
               else self.gamma_max)
        gamma_eff = 0 if fused else min(cap, max(1, int(dec.gamma)))
        # tree sessions additionally honor the decision's branch width,
        # clamped to the compiled bound; linear sessions pin b = 1 so a
        # tree-aware policy driving a linear session stays harmless
        if self.max_branches and not fused:
            self._branches_eff = min(self.max_branches,
                                     max(1, int(getattr(dec, "branches", 1))))
        else:
            self._branches_eff = 1
        self._branches_prev = float(self._branches_eff)
        if self.log_gamma:
            self.gamma_seq.append(1 if fused else gamma_eff)
        if fused:
            self.fused_iterations += 1
        else:
            self.gamma_sum += gamma_eff
            self.gamma_rounds += 1
        self._gamma_prev = 1.0 if fused else float(gamma_eff)
        return gamma_eff, fused

    def run_chunk(self, policy, max_iters: Optional[int] = None,
                  q_depth: float = 0.0) -> int:
        """Dispatch up to ``sync_every`` speculation rounds, then sync the
        host: cursors/done flags come off-device once, acceptance bits are
        attributed to the request occupying each slot (``num_new == 0``
        rows were inactive), and window-policy features update. Returns the
        number of iterations run.

        With a ``transport``, each round is a real draft→verify→verdict
        exchange between the engine's split workers
        (:meth:`_run_chunk_transport`); otherwise the engine's fused
        colocated step runs with ``sync_every`` iterations in flight.
        Both paths honor ``WindowDecision.mode`` — a fused decision
        commits target-only tokens (the colocated step still pays the
        draft proposal compute, which is masked dead weight there; the
        transport path skips the draft and the round trip entirely).
        ``mode_policy="pipeline"`` overlaps consecutive distributed rounds
        over the full-duplex transport (:meth:`_run_chunk_pipeline`); the
        half-duplex exchange stays the default."""
        active = sum(1 for r in self._slots if r is not None and not r.done)
        with span("chunk", active=active):
            if self.transport is None:
                return self._run_chunk_colocated(policy, max_iters, q_depth)
            if self.mode_policy == "pipeline":
                return self._run_chunk_pipeline(policy, max_iters, q_depth)
            return self._run_chunk_transport(policy, max_iters, q_depth)

    def _run_chunk_colocated(self, policy, max_iters: Optional[int],
                             q_depth: float) -> int:
        """Up to ``sync_every`` rounds of the engine's fused colocated
        step, dispatched back to back, then one host sync."""
        n = self.sync_every
        if max_iters is not None:
            n = min(n, max_iters - self.iterations)
        if n <= 0 or not self.occupied:
            return 0
        eng = self.engine
        tree = bool(self.max_branches)
        step = (eng._tree_step(self.gamma_max, self.max_branches) if tree
                else eng._step_fn(self.gamma_max))
        chunk_t0 = time.perf_counter()
        chunk_gammas: list[int] = []
        for r in range(n):
            gamma, fused = self._decide(policy, q_depth)
            chunk_gammas.append(gamma)
            with span("round", gamma=gamma, fused=fused):
                self._key, ks = jax.random.split(self._key)
                with span("step.dispatch"):
                    if tree:
                        # γ = 0 (fused decision) masks every non-anchor node:
                        # only the target's own next token commits, same as
                        # the linear step's fused round
                        (self._state, self._out_buf, self._cursor, self._nacc,
                         self._nn, self._done) = step(
                            eng.draft_params, eng.target_params,
                            self._state, ks,
                            jnp.asarray(gamma, jnp.int32),
                            jnp.asarray(self._branches_eff, jnp.int32),
                            jnp.asarray(r, jnp.int32),
                            self._out_buf, self._cursor, self._nacc, self._nn,
                            self._max_new, self._done,
                            jnp.asarray(self.eos_id, jnp.int32))
                    else:
                        (self._state, self._out_buf, self._cursor, self._nacc,
                         self._nn, self._done) = step(
                            eng.draft_params, eng.target_params,
                            self._state, ks,
                            jnp.asarray(gamma, jnp.int32),
                            jnp.asarray(r, jnp.int32),
                            self._out_buf, self._cursor, self._nacc, self._nn,
                            self._max_new, self._done,
                            jnp.asarray(self.eos_id, jnp.int32))
            self.iterations += 1
        self._sync_and_attribute(n, chunk_gammas, chunk_t0,
                                 non_target_ms=0.0,
                                 colocated_rtt_ms=eng.rtt_ms)
        return n

    def _verify_commit_round(self, tw, window_np: np.ndarray, gamma: int,
                             row_idx: int, q_probs, sampled: bool, key):
        """Run the TargetWorker's verify/commit program on one window
        against the session's ground-truth target-side buffers (cache,
        output buffer, cursors, lifecycle flags — all updated in place).
        Shared by the half-duplex and pipelined transport paths."""
        eng = self.engine
        state = self._state
        args = [tw.params, state.target_cache, jnp.asarray(window_np),
                state.pos, jnp.asarray(gamma, jnp.int32), key]
        if sampled:
            if q_probs is None:       # fused round: q is never read
                if self._q_zero is None:
                    self._q_zero = jnp.zeros(
                        (self.capacity, self.gamma_max, eng.draft_cfg.vocab),
                        jnp.float32)
                q_probs = self._q_zero
            args.append(q_probs)
        with span("verify", gamma=gamma):
            (tcache, new_pos, new_last, self._out_buf, self._cursor,
             self._nacc, self._nn, self._done, num_new_dev, nacc_dev,
             next_raw) = tw.verify_commit(self.gamma_max)(
                *args, self._out_buf, self._cursor, self._nacc, self._nn,
                self._max_new, self._done,
                jnp.asarray(row_idx, jnp.int32), jnp.asarray(self.eos_id,
                                                             jnp.int32))
        return tcache, new_pos, new_last, num_new_dev, nacc_dev, next_raw

    def _verify_commit_tree_round(self, tw, tree_np: np.ndarray, gamma: int,
                                  branches: int, row_idx: int):
        """Tree analogue of :meth:`_verify_commit_round`: one ancestor-
        masked verify pass + longest-accepted-root-path verdict + winning-
        path KV relocation on the target cache. Returns the winning path
        too — the draft side relocates its propose cache with it."""
        state = self._state
        with span("verify", gamma=gamma):
            (tcache, new_pos, new_last, self._out_buf, self._cursor,
             self._nacc, self._nn, self._done, num_new_dev, nacc_dev,
             next_raw, path_dev) = tw.verify_commit_tree(
                self.gamma_max, self.max_branches)(
                tw.params, state.target_cache, jnp.asarray(tree_np),
                state.pos, jnp.asarray(gamma, jnp.int32),
                jnp.asarray(branches, jnp.int32),
                self._out_buf, self._cursor, self._nacc, self._nn,
                self._max_new, self._done, jnp.asarray(row_idx, jnp.int32),
                jnp.asarray(self.eos_id, jnp.int32))
        return (tcache, new_pos, new_last, num_new_dev, nacc_dev, next_raw,
                path_dev)

    def _fused_round(self, dw, tw, row_idx: int, sampled: bool, key) -> float:
        """One fused (cloud-only) round over the transport: γ = 0 verify
        commits the target's own next token, the draft ingests it so its
        cache stays coherent for a later distributed round, and tokens
        stream edge-ward one control round trip per ``FUSED_FLUSH_TOKENS``
        committed tokens — the same per-chunk amortization DSD-Sim charges
        (``fused_chunk``; per-request streams overlap on the link in the
        sim, so batch-level amortization approximates their wall-clock
        cost). Returns the unhidden link delay imposed by stream flushes."""
        state = self._state
        window_np = np.zeros((self.capacity, self.gamma_max + 1), np.int32)
        with span("verdict.sync"):
            window_np[:, 0] = np.asarray(state.last_token)
        (tcache, new_pos, new_last, num_new_dev, _nacc, _next) = \
            self._verify_commit_round(tw, window_np, 0, row_idx, None,
                                      sampled, key)
        with span("ingest"):
            dcache = dw.ingest()(dw.params, state.draft_cache,
                                 state.last_token, state.pos, num_new_dev)
        link_ms = 0.0
        with span("verdict.sync"):
            self._fused_pending += int(np.asarray(num_new_dev).sum())
        while self._fused_pending >= FUSED_FLUSH_TOKENS:
            link_ms += self.transport.control_roundtrip()
            self._fused_pending -= FUSED_FLUSH_TOKENS
        self._state = SpecDecodeState(draft_cache=dcache, target_cache=tcache,
                                      last_token=new_last, pos=new_pos)
        return link_ms

    def _tree_round(self, dw, tw, gamma: int, n_active: int, row_idx: int
                    ) -> tuple[float, float, np.ndarray]:
        """One tree round over the transport: the grid window crosses the
        wire with its parent table (node-count-priced payload), the verdict
        carries the winning path back so the draft can relocate its
        propose cache identically to the target's commit. Returns (link
        delay ms, draft ms, done flags on the host)."""
        from ..distributed.wire import VerdictMsg, WindowMsg
        tr = self.transport
        state = self._state
        b = self._branches_eff
        t_draft = time.perf_counter()
        with span("propose", gamma=gamma):
            toks, dcache_prop = dw.propose_tree(
                self.gamma_max, self.max_branches)(
                dw.params, state.draft_cache, state.last_token, state.pos)
            toks_np = np.asarray(toks)
        draft_ms = (time.perf_counter() - t_draft) * 1e3
        rid = self._round_seq
        self._round_seq += 1
        msg = WindowMsg(tokens=toks_np, gamma=gamma, n_active=n_active,
                        round_id=rid, n_nodes=toks_np.shape[1], branches=b,
                        parent=self._tree_spec.parent_np)
        link_ms = tr.send_window(msg)
        (tcache, new_pos, new_last, num_new_dev, nacc_dev,
         next_raw, path_dev) = self._verify_commit_tree_round(
            tw, msg.tokens, gamma, b, row_idx)
        with span("verdict.sync"):
            done_host = np.asarray(self._done)
            verdict = VerdictMsg(
                n_accepted=np.asarray(nacc_dev),
                num_new=np.asarray(num_new_dev),
                next_token=np.asarray(next_raw),
                last_token=np.asarray(new_last),
                done=done_host, gamma=gamma, n_active=n_active,
                round_id=rid, path=np.asarray(path_dev))
        link_ms += tr.send_verdict(verdict)
        with span("ingest"):
            dcache = dw.ingest_tree(self.gamma_max, self.max_branches)(
                dcache_prop, state.pos, jnp.asarray(verdict.path),
                jnp.asarray(verdict.n_accepted))
        self._state = SpecDecodeState(draft_cache=dcache, target_cache=tcache,
                                      last_token=new_last, pos=new_pos)
        return link_ms, draft_ms, done_host

    def _linear_round(self, dw, tw, gamma: int, n_active: int, row_idx: int,
                      kd, kv) -> tuple[float, float, np.ndarray]:
        """One half-duplex linear round over the transport: propose, ship
        the window, verify/commit, ship the verdict, bring the draft's
        cache to the committed prefix. Returns (link delay ms, draft ms,
        done flags on the host)."""
        from ..distributed.wire import VerdictMsg, WindowMsg
        G = self.gamma_max
        tr = self.transport
        sampled = self.engine.temperature > 0.0
        state = self._state
        # timing the propose dispatch through the host materialize
        # isolates the draft's serial scan — excluded from the TPOT
        # feature like the sim excludes its draft time
        t_draft = time.perf_counter()
        with span("propose", gamma=gamma):
            toks, q_probs, dcache_prop = dw.propose(G)(
                dw.params, state.draft_cache, state.last_token, state.pos, kd)
            toks_np = np.asarray(toks)
        draft_ms = (time.perf_counter() - t_draft) * 1e3
        rid = self._round_seq
        self._round_seq += 1
        msg = WindowMsg(tokens=toks_np, gamma=gamma, n_active=n_active,
                        q_probs=q_probs if sampled else None, round_id=rid)
        link_ms = tr.send_window(msg)
        window_np = np.concatenate(
            [np.asarray(state.last_token)[:, None], msg.tokens], axis=1)
        (tcache, new_pos, new_last, num_new_dev, nacc_dev,
         next_raw) = self._verify_commit_round(
            tw, window_np, gamma, row_idx, q_probs if sampled else None,
            sampled, kv)
        with span("verdict.sync"):
            done_host = np.asarray(self._done)
            verdict = VerdictMsg(
                n_accepted=np.asarray(nacc_dev),
                num_new=np.asarray(num_new_dev),
                next_token=np.asarray(next_raw),
                last_token=np.asarray(new_last),
                done=done_host, gamma=gamma, n_active=n_active,
                round_id=rid)
        link_ms += tr.send_verdict(verdict)
        if dw.attention:
            dcache = dcache_prop   # pos_map masks the stale tail
        else:
            # recurrent draft: re-advance the pre-window checkpoint over
            # the committed prefix. The correction token never enters the
            # advance (it is committed at position pos+num_new−1 and only
            # processed by the NEXT round), so the [anchor, proposals]
            # window is the advance input.
            with span("advance"):
                dcache = dw.advance(G)(dw.params, state.draft_cache,
                                       jnp.asarray(window_np), state.pos,
                                       num_new_dev)
        self._state = SpecDecodeState(draft_cache=dcache, target_cache=tcache,
                                      last_token=new_last, pos=new_pos)
        return link_ms, draft_ms, done_host

    def _run_chunk_transport(self, policy, max_iters: Optional[int],
                             q_depth: float) -> int:
        """Up to ``sync_every`` HALF-DUPLEX speculation rounds over the
        transport (the default exchange; ``mode_policy="pipeline"`` routes
        to :meth:`_run_chunk_pipeline` instead).

        Per distributed round: the DraftWorker proposes γ_max tokens, the
        token ids materialize on the host and cross the transport as a
        :class:`~repro.distributed.wire.WindowMsg` (paying the link's
        imposed delay), the TargetWorker verifies/commits, and the
        :class:`~repro.distributed.wire.VerdictMsg` pays the return delay.
        A fused round skips the draft and both hops
        (:meth:`_fused_round`). The per-round host sync is inherent —
        tokens must exist as bytes to cross a wire — so this path pays a
        full RTT of dead time per committed window; hiding it is exactly
        what the pipelined mode is for."""
        n = self.sync_every
        if max_iters is not None:
            n = min(n, max_iters - self.iterations)
        if n <= 0 or not self.occupied:
            return 0
        eng = self.engine
        dw, tw = eng.split_workers()
        B = self.capacity
        tr = self.transport
        sampled = eng.temperature > 0.0
        chunk_t0 = time.perf_counter()
        chunk_gammas: list[int] = []
        link_ms = 0.0
        draft_ms = 0.0
        done_host = np.asarray(self._done)
        it_run = 0
        for r in range(n):
            if done_host.all():
                break
            gamma, fused = self._decide(policy, q_depth)
            n_active = int(B - done_host.sum())
            with span("round", gamma=gamma, fused=fused):
                self._key, ks = jax.random.split(self._key)
                kd, kv = jax.random.split(ks)
                if fused:
                    link_ms += self._fused_round(dw, tw, r, sampled, kv)
                    with span("verdict.sync"):
                        done_host = np.asarray(self._done)
                else:
                    if self.max_branches:
                        out = self._tree_round(dw, tw, gamma, n_active, r)
                    else:
                        out = self._linear_round(dw, tw, gamma, n_active, r,
                                                 kd, kv)
                    link_ms += out[0]
                    draft_ms += out[1]
                    done_host = out[2]
            chunk_gammas.append(gamma)
            self.iterations += 1
            it_run += 1
        if it_run == 0:
            return 0
        if self._fused_pending and done_host.all():
            # the batch drained: flush the sub-chunk tail of fused tokens
            # so short fused outputs still pay their stream delivery (a
            # session abandoned mid-stream drains the tail in snapshot())
            link_ms += tr.control_roundtrip()
            self._fused_pending = 0
        self.link_ms += link_ms
        # the TPOT feature tracks TARGET service time: subtract the
        # measured draft proposal time and the link delay (only when the
        # transport really slept it into wall time — a non-sleeping
        # transport's delay goes to the virtual clock instead)
        self._sync_and_attribute(
            it_run, chunk_gammas, chunk_t0,
            non_target_ms=draft_ms + (link_ms if tr.wall_clock else 0.0),
            virtual_extra_ms=0.0 if tr.wall_clock else link_ms)
        return it_run

    # ----------------------------------------------------- pipelined decode

    def _make_window(self, dw, state: SpecDecodeState, gamma: int,
                     done_host: np.ndarray, cursor_host: np.ndarray,
                     speculative: bool) -> dict:
        """Propose one speculation window from ``state``, post it on the
        transport, and precompute BOTH resolutions of its verdict:

        - the OPTIMISTIC post-round state (all ``gamma`` proposals
          accepted, bonus token = the reserved (γ+1)-th proposal the draft
          would anchor its next window on), including a host mirror of
          :func:`repro.core.specdec.slot_stop_mask` so budget/EOS clamps
          are predicted exactly — a verdict matching the predicted
          ``(num_new, last_token, done)`` triple implies the optimistic
          draft state is bitwise the committed one (the draft's cache
          advance only ever consumes the anchor + accepted window prefix,
          all of which the triple pins down);
        - the ROLLBACK materials (pre-window checkpoint + window) that
          reconstruct the exact half-duplex post-verdict state on a miss.
        """
        from ..distributed.wire import WindowMsg
        eng = self.engine
        G = self.gamma_max
        B = self.capacity
        sampled = eng.temperature > 0.0
        max_new_host = np.asarray(self._max_new)
        t0 = time.perf_counter()
        with span("propose", gamma=gamma):
            self._key, kd = jax.random.split(self._key)
            toks, q_probs, dcache_prop = dw.propose(G)(
                dw.params, state.draft_cache, state.last_token, state.pos,
                kd)
            toks_np = np.asarray(toks)
            last_host = np.asarray(state.last_token)
        window_np = np.concatenate([last_host[:, None], toks_np], axis=1)

        # -- optimistic post-round prediction (slot_stop_mask mirror) ------
        active = ~done_host
        bonus = toks_np[:, gamma]
        committed = np.full((B, G + 1), -1, np.int32)
        committed[:, :gamma] = toks_np[:, :gamma]
        committed[:, gamma] = bonus
        num_eff = np.where(
            active,
            np.minimum(gamma + 1, np.maximum(0, max_new_host - cursor_host)),
            0).astype(np.int32)
        eos = self.eos_id
        ar = np.arange(G + 1)[None, :]
        is_eos = (committed == eos) & (ar < num_eff[:, None]) & (eos >= 0)
        has_eos = is_eos.any(axis=1)
        eos_pos = is_eos.argmax(axis=1).astype(np.int32)
        num_eff = np.where(has_eos, np.minimum(num_eff, eos_pos + 1),
                           num_eff).astype(np.int32)
        done_opt = done_host | (cursor_host + num_eff >= max_new_host) \
            | has_eos
        last_opt = np.where(done_host, last_host, bonus).astype(np.int32)
        num_eff_dev = jnp.asarray(num_eff)
        if dw.attention:
            opt_cache = dcache_prop    # pos_map masks the stale tail
        else:
            # recurrent draft: optimistic re-advance of the pre-window
            # checkpoint over the assumed-committed prefix — the same
            # jitted program a miss's rollback runs (zero recompiles)
            with span("advance"):
                opt_cache = dw.advance(G)(dw.params, state.draft_cache,
                                          jnp.asarray(window_np), state.pos,
                                          num_eff_dev)
        draft_ms = (time.perf_counter() - t0) * 1e3

        rid = self._round_seq
        self._round_seq += 1
        msg = WindowMsg(tokens=toks_np, gamma=gamma,
                        n_active=int(B - done_host.sum()),
                        q_probs=q_probs if sampled else None,
                        round_id=rid, speculative=speculative)
        self.transport.post_window(msg)
        return dict(
            msg=msg, gamma=gamma, round_id=rid, draft_ms=draft_ms,
            q_probs=q_probs if sampled else None,
            window_dev=jnp.asarray(window_np),
            base_pos=state.pos,              # pre-window position (rollback)
            ckpt_cache=state.draft_cache,    # recurrent rollback checkpoint
            prop_cache=dcache_prop,          # attention rollback basis
            opt_state=SpecDecodeState(
                draft_cache=opt_cache, target_cache=None,
                last_token=jnp.asarray(last_opt),
                pos=state.pos + num_eff_dev),
            opt_num_new=num_eff, opt_done=done_opt, opt_last=last_opt)

    def _run_chunk_pipeline(self, policy, max_iters: Optional[int],
                            q_depth: float) -> int:
        """Up to ``sync_every`` CROSS-ROUND PIPELINED speculation rounds:
        while the target verifies window k, the draft optimistically
        drafts window k+1 from its own proposed continuation and posts it
        speculatively on the full-duplex transport, so the draft scan and
        the window's outbound hop overlap window k's verification and
        verdict flight instead of serializing after them.

        On verdict arrival the optimistic prediction is checked against
        the actual ``(num_new, last_token, done)`` triple: a HIT keeps the
        pipelined window (it becomes the in-flight exchange — its verify
        starts without waiting a draft scan + upload); a MISS (partial or
        zero accept, bonus-token mismatch, or a mispredicted budget/EOS
        stop) discards the in-flight window unverified and rolls the
        draft's recurrent/KV state back to the commit point — attention
        drafts reuse the kept pre-speculation propose cache, recurrent
        drafts re-advance the pre-window checkpoint, both bitwise equal to
        the half-duplex state (at temperature 0 committed tokens are
        bit-identical to the half-duplex path by construction: the target
        only ever verifies windows whose anchor matches its committed
        prefix). In-flight speculation never crosses a chunk boundary, so
        admissions/retirements at ``sync_every`` granularity can never
        invalidate a window the transport still carries."""
        n = self.sync_every
        if max_iters is not None:
            n = min(n, max_iters - self.iterations)
        if n <= 0 or not self.occupied:
            return 0
        from ..distributed.wire import VerdictMsg
        eng = self.engine
        dw, tw = eng.split_workers()
        G = self.gamma_max
        tr = self.transport
        sampled = eng.temperature > 0.0
        chunk_t0 = time.perf_counter()
        chunk_gammas: list[int] = []
        link_ms = 0.0
        draft_ms = 0.0
        done_host = np.asarray(self._done)
        cursor_host = np.asarray(self._cursor).copy()
        it_run = 0
        pending = None   # posted window whose verdict is outstanding
        carry = None     # (γ, fused) decided during the previous flight
        while it_run < n and not done_host.all():
            if pending is None:
                gamma, fused = (carry if carry is not None
                                else self._decide(policy, q_depth))
                carry = None
            else:
                gamma, fused = pending["gamma"], False
            with span("round", gamma=gamma, fused=fused):
                if fused:
                    self._key, kf = jax.random.split(self._key)
                    link_ms += self._fused_round(dw, tw, it_run, sampled, kf)
                    with span("verdict.sync"):
                        done_host = np.asarray(self._done)
                        # the fused round advanced the device cursors:
                        # refresh the host mirror or later optimistic
                        # budget/EOS predictions in this chunk would run
                        # understated and force spurious rollbacks near the
                        # budget edge
                        cursor_host = np.asarray(self._cursor).copy()
                    chunk_gammas.append(0)
                    self.iterations += 1
                    it_run += 1
                    continue
                if pending is None:
                    pending = self._make_window(dw, self._state, gamma,
                                                done_host, cursor_host,
                                                speculative=False)
                    draft_ms += pending["draft_ms"]

                # -- target: receive + verify the in-flight window --------
                wmsg, waited = tr.recv_window()
                link_ms += waited
                with span("verdict.sync"):
                    last_host = np.asarray(self._state.last_token)
                window_np = np.concatenate([last_host[:, None], wmsg.tokens],
                                           axis=1)
                self._key, kv = jax.random.split(self._key)
                (tcache, new_pos, new_last, num_new_dev, nacc_dev,
                 next_raw) = self._verify_commit_round(
                    tw, window_np, wmsg.gamma, it_run, pending["q_probs"],
                    sampled, kv)
                with span("verdict.sync"):
                    verdict = VerdictMsg(
                        n_accepted=np.asarray(nacc_dev),
                        num_new=np.asarray(num_new_dev),
                        next_token=np.asarray(next_raw),
                        last_token=np.asarray(new_last),
                        done=np.asarray(self._done), gamma=wmsg.gamma,
                        n_active=wmsg.n_active, round_id=wmsg.round_id)
                tr.post_verdict(verdict)

                # -- draft: speculate window k+1 while the verdict flies ---
                spec = None
                if it_run + 1 < n and not pending["opt_done"].all():
                    gamma2, fused2 = self._decide(policy, q_depth)
                    if fused2:
                        carry = (gamma2, fused2)   # fused runs unpipelined
                    else:
                        spec = self._make_window(
                            dw, pending["opt_state"], gamma2,
                            pending["opt_done"],
                            cursor_host + pending["opt_num_new"],
                            speculative=True)
                        draft_ms += spec["draft_ms"]

                # -- resolve the verdict ----------------------------------
                _vmsg, waited = tr.recv_verdict()
                link_ms += waited
                hit = (np.array_equal(verdict.num_new,
                                      pending["opt_num_new"])
                       and np.array_equal(verdict.done, pending["opt_done"])
                       and np.array_equal(verdict.last_token,
                                          pending["opt_last"]))
                if hit:
                    self.pipeline_hits += 1
                    self._pipe_recent.append(1.0)
                    dcache = pending["opt_state"].draft_cache
                else:
                    self.pipeline_misses += 1
                    self._pipe_recent.append(0.0)
                    if dw.attention:
                        dcache = pending["prop_cache"]
                    else:
                        with span("advance"):
                            dcache = dw.advance(G)(
                                dw.params, pending["ckpt_cache"],
                                pending["window_dev"], pending["base_pos"],
                                num_new_dev)
                self._state = SpecDecodeState(
                    draft_cache=dcache, target_cache=tcache,
                    last_token=new_last, pos=new_pos)
                done_host = verdict.done
                cursor_host = cursor_host + verdict.num_new
                chunk_gammas.append(wmsg.gamma)
                self.iterations += 1
                it_run += 1
                if hit and spec is not None:
                    pending = spec            # the pipelined window is live
                else:
                    if spec is not None:      # late verdict invalidates it
                        tr.discard_window()
                        # the re-draft reuses the invalidated window's γ
                        # decision (it was made pre-verdict — that is what
                        # pipelining means), keeping policy calls and
                        # gamma_seq 1:1 with committed rounds
                        carry = (spec["gamma"], False)
                    pending = None
        if carry is not None:
            # a decision was made for a round that never ran (the batch
            # drained or the chunk ended first): unwind its bookkeeping
            if carry[1]:
                self.fused_iterations -= 1
            else:
                self.gamma_sum -= carry[0]
                self.gamma_rounds -= 1
            if self.log_gamma and self.gamma_seq:
                self.gamma_seq.pop()
        if it_run == 0:
            return 0
        if self._fused_pending and done_host.all():
            link_ms += tr.control_roundtrip()
            self._fused_pending = 0
        self.link_ms += link_ms
        del self._pipe_recent[:-16]
        self._sync_and_attribute(
            it_run, chunk_gammas, chunk_t0,
            non_target_ms=draft_ms + (link_ms if tr.wall_clock else 0.0),
            virtual_extra_ms=0.0 if tr.wall_clock else link_ms)
        return it_run

    def _sync_and_attribute(self, n: int, chunk_gammas: list[int],
                            chunk_t0: float, non_target_ms: float,
                            virtual_extra_ms: float = 0.0,
                            colocated_rtt_ms: float = 0.0) -> None:
        """Chunk epilogue shared by the colocated and transport paths: one
        host transfer of cursors/flags/stat rows, per-request acceptance
        attribution, window-policy feature update. ``chunk_gammas`` holds
        the EFFECTIVE per-round γ (0 for fused rounds, which propose
        nothing — their commits enter token counts but not acceptance
        stats). ``non_target_ms`` (imposed link delay + measured draft
        proposal time) is excluded from the TPOT feature so it tracks
        target service time, matching what DSD-Sim's analyzer feeds AWC;
        the link shows up in ``rtt_recent_ms`` instead.

        Virtual-clock network accounting: the transport path passes its
        imposed-but-not-slept delay as ``virtual_extra_ms``; the colocated
        path passes ``colocated_rtt_ms`` and is billed one RTT per
        distributed round plus the per-token amortized stream flush for
        fused commits — the same charges the transport path and DSD-Sim
        make, so ``virtual_ms`` stays comparable across paths."""
        with span("chunk.sync"):
            cur = np.asarray(self._cursor)
            done = np.asarray(self._done)
            nacc = np.asarray(self._nacc[:n])
            nn = np.asarray(self._nn[:n])
        # wall time is measured AFTER the blocking host transfers above:
        # the colocated loop dispatches its jitted steps asynchronously,
        # so the chunk's device compute only completes here
        chunk_wall = time.perf_counter() - chunk_t0

        for r in range(n):
            act = nn[r] > 0
            n_act = int(act.sum())
            if n_act and chunk_gammas[r] > 0:
                self._alpha_recent.append(
                    float(nacc[r][act].sum()) / (chunk_gammas[r] * n_act))
                self.proposed += chunk_gammas[r] * n_act
        self.accepted += int(nacc.sum())

        chunk_tokens = 0
        for j, rec in enumerate(self._slots):
            if rec is None:
                continue
            for r in range(n):
                ne = int(nn[r, j])
                if ne > 0 and chunk_gammas[r] > 0:
                    # n_accepted is pre-clamped to committed tokens; a
                    # reject bit exists only when a correction token was
                    # actually committed (num_new exceeded the accepted
                    # prefix without the window being fully accepted)
                    na = int(nacc[r, j])
                    rec.bits.extend([1] * na)
                    if ne > na and na < chunk_gammas[r]:
                        rec.bits.append(0)
                    rec.proposed += chunk_gammas[r]
                    rec.accepted += na
            chunk_tokens += int(cur[j]) - rec.produced
            rec.produced = int(cur[j])
            rec.done = bool(done[j])

        active_iters = int((nn > 0).sum())
        # the chunk's counters, as a marker in the trace
        with span("chunk.commit", tokens=chunk_tokens,
                  slot_rounds=active_iters,
                  fused=sum(1 for g in chunk_gammas if g == 0)):
            pass
        mean_tok = chunk_tokens / max(1, active_iters)
        compute_ms = max(0.0, chunk_wall * 1e3 - non_target_ms)
        self._tpot_recent.append((compute_ms / n) / max(1.0, mean_tok))
        del self._alpha_recent[:-16], self._tpot_recent[:-16]
        if colocated_rtt_ms > 0.0:
            n_dist = sum(1 for g in chunk_gammas if g > 0)
            fused_tokens = int(sum(nn[r].sum() for r in range(n)
                                   if chunk_gammas[r] == 0))
            virtual_extra_ms += colocated_rtt_ms * (
                n_dist + fused_tokens / FUSED_FLUSH_TOKENS)
        self.virtual_ms += virtual_extra_ms + chunk_wall * 1e3
        self.decode_wall_s += chunk_wall

    def _features(self, q_depth: float) -> FeatureSnapshot:
        a = self._alpha_recent[-16:]
        t = self._tpot_recent[-16:]
        p = self._pipe_recent[-16:]
        if self.transport is not None:
            rtt = self.transport.recent_rtt_ms
        else:
            rtt = self.engine.rtt_ms
        return FeatureSnapshot(
            q_depth=q_depth,
            alpha_recent=(sum(a) / len(a)) if a else 0.7,
            rtt_recent_ms=rtt,
            tpot_recent_ms=(sum(t) / len(t)) if t else 50.0,
            gamma_prev=self._gamma_prev,
            # outside pipeline mode no RTT is ever overlapped: report 0 so
            # bootstrap_gamma's overlapped-RTT term stays inert
            pipe_hit_recent=((sum(p) / len(p)) if p else 0.0)
            if self.mode_policy == "pipeline" else 0.0,
            branches_prev=self._branches_prev if self.max_branches else 1.0)

    # ------------------------------------------------------------ retirement

    def retire(self, slot: int, scrub: bool = False
               ) -> tuple[np.ndarray, SlotRecord]:
        """Extract a slot's committed tokens (ONE row transfer, length from
        the per-slot cursor) and free the slot. The device row stays inert
        (``done`` masks it) until the next admission overwrites it;
        ``scrub=True`` additionally resets the row's caches immediately so
        a long-lived session holds no retired request's KV."""
        rec = self._slots[slot]
        assert rec is not None, f"slot {slot} is empty"
        n = min(rec.produced, self.max_new_cap)
        tokens = np.asarray(self._out_buf[slot])[:n].astype(np.int64)
        self._slots[slot] = None
        if self.paged and self._slot_blocks[slot] is not None:
            # unmap BEFORE freeing: the frozen slot still writes its masked
            # speculative window every step, and the device stream orders
            # this release ahead of any later insert that reuses the blocks
            # (see models/kvcache.py module docstring)
            release = self.engine._release_step()
            self._state = release(self._state, jnp.asarray(slot, jnp.int32))
            for side, ids in self._slot_blocks[slot].items():
                self._alloc[side].free(ids)
            self._slot_blocks[slot] = None
        if scrub:
            self._state = reset_slot(self._state, slot)
        return tokens, rec

    # -------------------------------------------------------------- extract

    def snapshot(self) -> tuple[np.ndarray, GenerationStats]:
        """Wave-style extraction: the full output buffer plus engine-schema
        stats over currently-occupied slots (the ``generate()`` epilogue).
        Drains any sub-chunk tail of fused-mode tokens still pending
        stream delivery, so sessions that stop on the iteration bound pay
        the final control round trip too."""
        if self.transport is not None and self._fused_pending:
            self.link_ms += self.transport.control_roundtrip()
            self._fused_pending = 0
        tokens = np.asarray(self._out_buf).astype(np.int64) \
            if self._out_buf is not None \
            else np.empty((self.capacity, 0), np.int64)
        produced = np.array([r.produced if r else 0 for r in self._slots],
                            np.int64)
        n_occ = len(self.occupied)
        stats = GenerationStats(
            iterations=self.iterations, proposed=self.proposed,
            accepted=self.accepted,
            tokens=int(produced.sum()) - n_occ,
            prefill_s=self.prefill_s, virtual_ms=self.virtual_ms,
            acceptance_seqs=[r.bits for r in self._slots if r is not None],
            gamma_seq=list(self.gamma_seq), produced=produced,
            pipeline_hits=self.pipeline_hits,
            pipeline_misses=self.pipeline_misses)
        return tokens, stats
