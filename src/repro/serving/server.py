"""In-process serving on real JAX models — continuous (iteration-level)
batching over persistent :class:`repro.core.session.DecodeSession` pools,
with TOPOLOGY-FIRST multi-pair routing.

:class:`SpecDecodeServer` serves one deployment of **draft–target pairs**
(:class:`ServingPair`): each pair owns an engine, a window policy, an
optional transport (its edge–cloud link) and a mode policy, and runs its
own slot-based decode session. Requests are admitted into free slots the
moment they have arrived and a slot is open (admission policy mirroring
``sim/policies.py`` — FIFO or length-aware LAB within the chosen pair;
with ``ServerConfig.paged_kv`` admission is additionally block-aware: a
request enters only when every paged side has enough free KV blocks for
its prompt + decode budget, otherwise it waits for retirements),
routed across pairs by a pluggable :class:`PairRouter` (least-loaded by
default; routing is STICKY — a request never migrates off the pair that
admitted it). Decode proceeds in ``sync_every``-iteration chunks per pair
(pairs interleave chunk-by-chunk in one process), and finished requests
retire at chunk boundaries, freeing their slot for the next arrival
without stalling neighbours. This is the execution model DSD-Sim assumes
(``BatchingConfig.continuous=True`` plus per-pair links), so simulator
predictions and real execution are directly comparable — build both from
ONE :class:`repro.topology.ClusterSpec` and the comparison is a property
of the spec, not of per-benchmark plumbing.

The legacy single-pair surface is unchanged:
``SpecDecodeServer(engine, policy, cfg)`` wraps its arguments in a
one-pair deployment (``cfg.transport``/``cfg.mode_policy`` become the
pair's link and mode), and every admission/retirement decision is
bit-identical to the pre-topology server.

Per-request metrics include queue wait: TTFT runs from the request's own
``arrival_s`` to the end of its own prefill-insert (its anchor token), and
e2e to its retirement; token payloads come from the per-sequence cursor,
never from an assumed ``max_new_tokens``. Per-pair operating points
(mean γ, fused fraction, link bytes, measured RTT) are surfaced by
:meth:`SpecDecodeServer.pair_summaries` — heterogeneous links under one
server show per-pair AWC converging to different γ/fused mixes there.

:class:`WaveSpecDecodeServer` keeps the previous wave-batched execution
model (admit a wave, drain it fully, admit the next) as the measured
baseline: a long sequence holds every slot in its wave hostage, which is
exactly the sim↔real gap the continuous scheduler closes.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Optional, Protocol, Sequence

import numpy as np

from ..core.engine import SpecDecodeEngine
from ..core.session import DecodeSession
from ..core.window import StaticWindowPolicy, WindowPolicy
from ..tracing import span


@dataclass
class ServeRequest:
    request_id: int
    prompt: np.ndarray           # (S,) int32
    max_new_tokens: int
    arrival_s: float = 0.0       # relative to the serve-loop start
    request_class: str = ""      # fleet traffic class ("" = unclassified)
    slo_ttft_ms: float = 0.0     # per-request TTFT target (0 = no SLO)
    slo_tpot_ms: float = 0.0     # per-request TPOT target (0 = no SLO)


@dataclass
class ServeResult:
    request_id: int
    tokens: np.ndarray           # exactly the tokens produced (cursor-true)
    ttft_ms: float               # arrival → own first token (queue incl.)
    tpot_ms: float               # first token → finish, per later token
    e2e_ms: float                # arrival → retirement
    acceptance_rate: float
    queue_ms: float = 0.0        # arrival → admission start
    pair_id: str = ""            # draft–target pair that served the request
    request_class: str = ""      # carried from the request (SLO grading)
    slo_ttft_ms: float = 0.0
    slo_tpot_ms: float = 0.0
    shed: bool = False           # SLO admission dropped it (no tokens)


@dataclass
class ServingPair:
    """One deployed draft→target lane: engine + policy + link + mode.

    The runtime unit :func:`repro.topology.build_deployment` emits one of
    per :class:`repro.topology.PairSpec`; constructible directly for
    tests/benchmarks. ``pair_id`` doubles as the window policy's pair key,
    so adaptive policies (Dynamic/AWC) shared across pairs still keep one
    stabilizer per pair.

    A **process-backed** pair (``PairSpec.process: true``) carries no
    local engine or transport: ``host`` is a
    :class:`repro.distributed.host.PairHostHandle` driving draft/target
    worker processes over a :class:`~repro.distributed.SocketTransport`,
    and the server delegates the pair's share of the request stream to
    it."""
    pair_id: str
    engine: Optional[SpecDecodeEngine]
    policy: WindowPolicy
    transport: Optional[object] = None   # repro.distributed.Transport
    mode_policy: str = "auto"            # auto | distributed | fused | pipeline
    host: Optional[object] = None        # repro.distributed.host.PairHostHandle
    session: Optional[DecodeSession] = None  # live session, set by run() so
                                             # α/queue-aware routers can read
                                             # acceptance counters + occupancy
    draining: bool = False               # drained pairs admit nothing new


@dataclass
class ServerConfig:
    max_batch: int = 8           # slot-pool capacity PER PAIR
    length_aware: bool = True    # LAB admission (vs FIFO), as in sim
    pad_to: int = 16             # prompt padding quantum
    max_prompt_len: Optional[int] = None   # continuous pad bound
                                           # (default: queue max, rounded)
    max_new_cap: Optional[int] = None      # output width (default: queue max)
    eos_id: int = -1
    sync_every: Optional[int] = None       # admission/retirement granularity
    transport: Optional[object] = None     # legacy one-pair surface: the
                                           # implicit pair's Transport
    mode_policy: str = "auto"              # legacy one-pair surface: the
                                           # implicit pair's mode policy
    paged_kv: bool = False       # paged block-pool KV cache per pair
    kv_block_size: int = 16      # positions per KV block (paged only)
    kv_pool_blocks: Optional[object] = None  # pool size: int, or dict
                                             # {"draft": n, "target": n};
                                             # None = dense-parity sizing
    kv_quantize: bool = False    # int8 per-entry KV quantization (paged)
    slo_admission: str = "off"   # off | reroute | shed: when a pair's rolling
                                 # p95 TTFT drifts past a request's class SLO,
                                 # reroute it to a healthy pair (or shed it
                                 # outright when none exists and mode=shed)
    slo_min_samples: int = 8     # retirements per pair before SLO admission
                                 # trusts that pair's rolling p95
    slo_window: int = 256        # rolling-quantile window size per pair


# -- pair routing ------------------------------------------------------------

class PairRouter(Protocol):
    """Chooses the draft–target pair that admits a request.

    ``free_slots[i]`` is pair i's current free-slot count; the router must
    return an index with ``free_slots[i] > 0`` (the server only consults it
    while capacity exists somewhere). Routing is sticky by construction:
    the server never migrates an admitted request."""

    def route(self, req: ServeRequest, pairs: Sequence[ServingPair],
              free_slots: Sequence[int]) -> int: ...


class LeastLoadedPairRouter:
    """Default router: the pair with the most free slots (ties break to
    the lowest pair index, which keeps the one-pair case trivially exact
    and multi-pair admission deterministic)."""

    def route(self, req: ServeRequest, pairs: Sequence[ServingPair],
              free_slots: Sequence[int]) -> int:
        return int(max(range(len(free_slots)), key=lambda i: free_slots[i]))


class RoundRobinPairRouter:
    """Cycle over pairs, skipping the ones with no free slot."""

    def __init__(self):
        self._next = 0

    def route(self, req: ServeRequest, pairs: Sequence[ServingPair],
              free_slots: Sequence[int]) -> int:
        n = len(free_slots)
        for k in range(n):
            i = (self._next + k) % n
            if free_slots[i] > 0:
                self._next = i + 1
                return i
        return self._next % n


PAIR_ROUTERS = {
    "least-loaded": LeastLoadedPairRouter,
    "round-robin": RoundRobinPairRouter,
}

# the α/link/queue-aware fleet router registers here too (late import:
# repro.fleet.routing is dependency-free, so this cannot cycle)
from ..fleet.routing import SmartPairRouter  # noqa: E402
PAIR_ROUTERS["smart"] = SmartPairRouter


class _ArrivalClock:
    """Wall clock for the serve loop; ``wait_until`` idles to an arrival."""

    def __init__(self):
        self._t0 = time.perf_counter()

    def now(self) -> float:
        return time.perf_counter() - self._t0

    def wait_until(self, t_s: float) -> None:
        d = t_s - self.now()
        if d > 0:
            time.sleep(d)


class SpecDecodeServer:
    """Continuous slot-based scheduler over a deployment of draft–target
    pairs (one decode session per pair)."""

    def __init__(self, engine: Optional[SpecDecodeEngine] = None,
                 window_policy: Optional[WindowPolicy] = None,
                 cfg: Optional[ServerConfig] = None, *,
                 pairs: Optional[Sequence[ServingPair]] = None,
                 router: Optional[PairRouter] = None):
        self.cfg = cfg or ServerConfig()
        if pairs is None:
            assert engine is not None, \
                "pass either an engine (one-pair surface) or pairs="
            pairs = [ServingPair(
                pair_id="pair0", engine=engine,
                policy=window_policy or StaticWindowPolicy(4),
                transport=self.cfg.transport,
                mode_policy=self.cfg.mode_policy)]
        else:
            assert engine is None and window_policy is None, \
                "pairs= replaces the engine/window_policy surface"
            assert len(pairs) >= 1, "a deployment needs at least one pair"
            ids = [p.pair_id for p in pairs]
            assert len(set(ids)) == len(ids), f"duplicate pair ids: {ids}"
        hosted = [p.host is not None for p in pairs]
        assert all(hosted) or not any(hosted), \
            "process-backed and in-process pairs cannot mix in one server"
        assert all(p.engine is not None or p.host is not None for p in pairs), \
            "every pair needs an engine (in-process) or a host (process)"
        self._process_backed = all(hosted) and any(hosted)
        self.pairs = list(pairs)
        self.router = router or LeastLoadedPairRouter()
        # legacy attribute surface (bench/test introspection)
        self.engine = self.pairs[0].engine
        self.policy = self.pairs[0].policy
        self.queue: list[ServeRequest] = []
        self.results: list[ServeResult] = []
        self._sessions: list[DecodeSession] = []
        self._served = [0] * len(self.pairs)
        from ..fleet.stats import RollingQuantile
        self._ttft_q = [RollingQuantile(self.cfg.slo_window)
                        for _ in self.pairs]
        self._tpot_q = [RollingQuantile(self.cfg.slo_window)
                        for _ in self.pairs]
        self._shed = [0] * len(self.pairs)

    def submit(self, req: ServeRequest) -> None:
        self.queue.append(req)

    # -- drain / re-admit ----------------------------------------------------

    def drain(self, pair_id: str) -> None:
        """Stop routing NEW requests to a pair; in-flight sequences finish
        normally (routing is sticky, so nothing migrates off)."""
        self._pair_by_id(pair_id).draining = True

    def undrain(self, pair_id: str) -> None:
        """Re-admit a drained pair into the routable set."""
        self._pair_by_id(pair_id).draining = False

    def _pair_by_id(self, pair_id: str) -> ServingPair:
        for p in self.pairs:
            if p.pair_id == pair_id:
                return p
        raise KeyError(f"no pair {pair_id!r} in this deployment")

    # -- admission (FIFO vs LAB, mirroring sim/policies.py) ------------------

    def _select_admissions(self, arrived: list[ServeRequest],
                           k: int) -> list[ServeRequest]:
        """Pick ≤ k arrived requests for ONE pair: head-of-line always
        goes; LAB fills the remaining free slots with the requests whose
        prompt lengths are closest to the head's (minimum intra-pool
        padding waste), FIFO in arrival order — the same rule
        ``sim.policies.LengthAwareBatching`` applies to a wave."""
        if not arrived or k <= 0:
            return []
        head = arrived[0]
        if not self.cfg.length_aware:
            return arrived[:k]
        rest = sorted(arrived[1:],
                      key=lambda r: abs(len(r.prompt) - len(head.prompt)))
        return [head] + rest[:k - 1]

    # -- serve loop ----------------------------------------------------------

    def _make_session(self, pair: ServingPair,
                      pending: list[ServeRequest]) -> DecodeSession:
        q = self.cfg.pad_to
        mp = self.cfg.max_prompt_len or max(len(r.prompt) for r in pending)
        mp = ((mp + q - 1) // q) * q
        cap = self.cfg.max_new_cap or max(r.max_new_tokens for r in pending)
        eng = pair.engine
        gmax = eng.gamma_max or eng._policy_gamma_bound(pair.policy)
        return DecodeSession(eng, capacity=self.cfg.max_batch,
                             max_new_cap=cap, max_prompt_len=mp,
                             gamma_max=gmax,
                             sync_every=self.cfg.sync_every,
                             eos_id=self.cfg.eos_id, log_gamma=False,
                             transport=pair.transport,
                             mode_policy=pair.mode_policy,
                             pair_key=pair.pair_id,
                             paged=self.cfg.paged_kv,
                             kv_block_size=self.cfg.kv_block_size,
                             kv_pool_blocks=self.cfg.kv_pool_blocks,
                             kv_quantize=self.cfg.kv_quantize)

    def run(self) -> list[ServeResult]:
        """Drain the submitted stream; returns per-request results.

        Loop invariant per cycle: route + admit arrived requests into free
        slots (head-of-line request picks its pair via the router, LAB/FIFO
        co-admission fills that pair's remaining slots) → run one decode
        chunk per occupied pair → retire finished slots. When no request
        is in flight the loop idles to the next arrival instead of
        spinning.
        """
        if not self.queue:
            return self.results
        if self._process_backed:
            return self._run_process_backed()
        pending = sorted(self.queue, key=lambda r: r.arrival_s)
        self.queue = []
        sessions = [self._make_session(p, pending) for p in self.pairs]
        self._sessions = sessions
        for pair, sess in zip(self.pairs, sessions):
            pair.session = sess     # routers read live acceptance/occupancy
        self._served = [0] * len(self.pairs)
        # request_id -> (request, admit_start_s, first_token_s, pair_idx)
        in_flight: dict[int, tuple[ServeRequest, float, float, int]] = {}
        clock = _ArrivalClock()
        # arrival_s is measured from the clock's start, so the span opens
        # with it: a request is due at the span's start + its arrival_s
        with span("serve", requests=len(pending)):
            while pending or any(s.occupied for s in sessions):
                arrived = self._admit_arrived(sessions, pending, in_flight,
                                              clock)
                if not any(s.occupied for s in sessions):
                    with span("serve.idle"):
                        clock.wait_until(min(r.arrival_s for r in pending))
                    continue
                # q_depth: requests that have ARRIVED and wait for a slot —
                # future arrivals must not leak into policy features
                q_depth = len(arrived) / max(1, 4 * self.cfg.max_batch)
                for idx, sess in enumerate(sessions):
                    if not sess.occupied:
                        continue
                    sess.run_chunk(self.pairs[idx].policy, q_depth=q_depth)
                    for j in sess.finished_slots():
                        with span("retire",
                                  request_id=sess.record(j).request_id):
                            self._retire(idx, sess, j, in_flight, clock)
        return self.results

    def _admit_arrived(self, sessions: list[DecodeSession],
                       pending: list[ServeRequest], in_flight: dict,
                       clock: _ArrivalClock) -> list[ServeRequest]:
        """Route and admit arrived requests into free slots (head-of-line
        request picks its pair via the router, LAB/FIFO co-admission fills
        that pair's remaining slots). Returns the arrived requests still
        waiting."""
        now = clock.now()
        arrived = [r for r in pending if r.arrival_s <= now]
        if (arrived and all(p.draining for p in self.pairs)
                and not any(s.occupied for s in sessions)):
            raise RuntimeError(
                "every pair is draining with requests still pending — "
                "undrain a pair to keep serving")
        while arrived:
            with span("serve.route", arrived=len(arrived)):
                # a draining pair advertises zero free slots: routers skip
                # it, in-flight sequences keep decoding until retirement
                frees = [0 if p.draining else len(s.free)
                         for p, s in zip(self.pairs, sessions)]
                if not any(frees):
                    break
                idx = self.router.route(arrived[0], self.pairs, frees)
                if frees[idx] <= 0:
                    break
                routed = self._apply_slo_admission(arrived, pending, idx,
                                                   frees, clock)
                if routed is None:
                    continue    # head shed; retry with the next head
                idx = routed
                chosen = self._select_admissions(arrived, frees[idx])
            admitted_any = False
            for r in chosen:
                # block-aware admission: a paged session may have a free
                # slot but not enough free KV blocks for this request's
                # budget — skip it and let retirements free blocks
                # (can_admit == slot check for dense sessions)
                if not sessions[idx].can_admit(len(r.prompt),
                                               r.max_new_tokens):
                    continue
                admit_start = clock.now()
                sessions[idx].admit(r.prompt, r.max_new_tokens,
                                    request_id=r.request_id,
                                    arrival_s=r.arrival_s)
                in_flight[r.request_id] = (r, admit_start, clock.now(), idx)
                pending.remove(r)
                arrived.remove(r)
                self._served[idx] += 1
                admitted_any = True
            if not admitted_any:
                break  # no capacity progress — decode to free blocks
        return arrived

    def _retire(self, idx: int, sess: DecodeSession, slot: int,
                in_flight: dict, clock: _ArrivalClock) -> None:
        """Retire a finished slot of pair ``idx`` and record its result."""
        tokens, rec = sess.retire(slot)
        r, admit_s, first_tok_s, _ = in_flight.pop(rec.request_id)
        end_s = clock.now()
        n = len(tokens)
        bits = rec.bits
        ttft = (first_tok_s - r.arrival_s) * 1e3
        tpot = (end_s - first_tok_s) * 1e3 / max(1, n - 1)
        self._ttft_q[idx].push(ttft)
        self._tpot_q[idx].push(tpot)
        self.results.append(ServeResult(
            request_id=r.request_id,
            tokens=tokens,
            ttft_ms=ttft,
            tpot_ms=tpot,
            e2e_ms=(end_s - r.arrival_s) * 1e3,
            acceptance_rate=(sum(bits) / len(bits)) if bits else 0.0,
            queue_ms=(admit_s - r.arrival_s) * 1e3,
            pair_id=self.pairs[idx].pair_id,
            request_class=r.request_class,
            slo_ttft_ms=r.slo_ttft_ms,
            slo_tpot_ms=r.slo_tpot_ms))

    # -- SLO-aware admission -------------------------------------------------

    def _slo_risky(self, idx: int, req: ServeRequest) -> bool:
        """Pair idx's rolling p95 TTFT has drifted past the request's SLO
        (only once enough retirements have been observed to trust it)."""
        if req.slo_ttft_ms <= 0:
            return False
        q = self._ttft_q[idx]
        return (len(q) >= self.cfg.slo_min_samples
                and q.p95() > req.slo_ttft_ms)

    def _apply_slo_admission(self, arrived: list[ServeRequest],
                             pending: list[ServeRequest], idx: int,
                             frees: Sequence[int],
                             clock: _ArrivalClock) -> Optional[int]:
        """SLO gate between routing and admission for the head-of-line
        request. Returns the (possibly rerouted) pair index, or None when
        the head was shed (mode=shed, no healthy pair). With
        ``slo_admission='off'`` this is the identity on ``idx``."""
        if self.cfg.slo_admission == "off":
            return idx
        head = arrived[0]
        if not self._slo_risky(idx, head):
            return idx
        # healthiest alternative with a free slot: unmeasured pairs count
        # as healthy (no evidence of drift), measured ones need p95 <= SLO
        best, best_p95 = None, None
        for i in range(len(self.pairs)):
            if i == idx or frees[i] <= 0 or self._slo_risky(i, head):
                continue
            p95 = self._ttft_q[i].p95()
            key = p95 if len(self._ttft_q[i]) else 0.0
            if best_p95 is None or key < best_p95:
                best, best_p95 = i, key
        if best is not None:
            return best
        if self.cfg.slo_admission != "shed":
            return idx      # reroute mode: nowhere better, admit anyway
        end_s = clock.now()
        self._shed[idx] += 1
        pending.remove(head)
        arrived.remove(head)
        self.results.append(ServeResult(
            request_id=head.request_id, tokens=np.zeros(0, np.int32),
            ttft_ms=float("inf"), tpot_ms=0.0,
            e2e_ms=(end_s - head.arrival_s) * 1e3, acceptance_rate=0.0,
            queue_ms=(end_s - head.arrival_s) * 1e3,
            pair_id=self.pairs[idx].pair_id,
            request_class=head.request_class,
            slo_ttft_ms=head.slo_ttft_ms, slo_tpot_ms=head.slo_tpot_ms,
            shed=True))
        return None

    def _run_process_backed(self) -> list[ServeResult]:
        """Drive process-backed pairs CONCURRENTLY: each pair's host
        handle serves its round-robin share of the request stream on its
        own thread, so the pairs' draft/target worker processes decode in
        true parallel (the whole point of ``PairSpec.process``). Wave
        batching per pair mirrors :class:`WaveSpecDecodeServer` — the
        continuous chunk scheduler needs an in-process session."""
        import threading

        pending = sorted(self.queue, key=lambda r: r.arrival_s)
        self.queue = []
        buckets: list[list[ServeRequest]] = [[] for _ in self.pairs]
        for i, r in enumerate(pending):
            buckets[i % len(self.pairs)].append(r)
        self._served = [len(b) for b in buckets]
        per_pair: list[list] = [[] for _ in self.pairs]
        errors: list[BaseException] = []

        def drive(idx: int) -> None:
            try:
                per_pair[idx] = self.pairs[idx].host.serve(buckets[idx])
            except BaseException as e:   # surface on the caller's thread
                errors.append(e)

        threads = [threading.Thread(target=drive, args=(i,), daemon=True)
                   for i in range(len(self.pairs)) if buckets[i]]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            raise errors[0]
        merged = [res for bucket in per_pair for res in bucket]
        merged.sort(key=lambda res: res.request_id)
        self.results.extend(merged)
        return self.results

    # -- per-pair observability ----------------------------------------------

    def pair_summaries(self) -> dict[str, dict]:
        """Per-pair operating point after :meth:`run`, keyed by pair id:
        request/iteration counts, mean effective γ, fused fraction,
        acceptance, pipeline hit counters, rolling p50/p95 TTFT/TPOT over
        the last ``slo_window`` retirements (the same windows SLO-aware
        admission consults; NaN until a retirement lands), and — when the
        pair has a transport — its link stats (bytes, messages, measured
        RTT)."""
        out: dict[str, dict] = {}
        if self._process_backed:
            for pair, served in zip(self.pairs, self._served):
                row = pair.host.summary()
                row["requests"] = served
                out[pair.pair_id] = row
            return out
        for i, (pair, sess, served) in enumerate(zip(self.pairs,
                                                     self._sessions,
                                                     self._served)):
            d = {
                "requests": served,
                "iterations": sess.iterations,
                "mean_gamma": round(sess.mean_gamma, 3),
                "fused_fraction": round(
                    sess.fused_iterations / max(1, sess.iterations), 4),
                "acceptance_rate": round(
                    sess.accepted / max(1, sess.proposed), 4),
                "pipeline_hits": sess.pipeline_hits,
                "pipeline_misses": sess.pipeline_misses,
                "link_ms": round(sess.link_ms, 2),
                "mode_policy": pair.mode_policy,
                "ttft_p50_ms": round(self._ttft_q[i].p50(), 3),
                "ttft_p95_ms": round(self._ttft_q[i].p95(), 3),
                "tpot_p50_ms": round(self._tpot_q[i].p50(), 3),
                "tpot_p95_ms": round(self._tpot_q[i].p95(), 3),
                "shed": self._shed[i],
            }
            fb = sess.free_kv_blocks()
            if fb is not None:
                d["free_kv_blocks"] = fb
            tr = pair.transport
            if tr is not None:
                d.update(
                    transport=tr.describe(),
                    bytes_sent=tr.bytes_sent,
                    messages=tr.messages_sent,
                    recent_rtt_ms=round(tr.recent_rtt_ms, 3))
            out[pair.pair_id] = d
        return out


class WaveSpecDecodeServer:
    """Wave-batched baseline: requests are admitted in waves of up to
    ``max_batch`` sequences (FIFO or LAB grouping), each wave runs
    ``engine.generate`` to the wave-max token budget, and the next wave
    starts only when the whole wave has drained. Kept as the measured
    baseline for ``benchmarks/bench_serving.py``; new code should use the
    continuous :class:`SpecDecodeServer`. Single-pair colocated only."""

    def __init__(self, engine: SpecDecodeEngine,
                 window_policy: Optional[WindowPolicy] = None,
                 cfg: Optional[ServerConfig] = None):
        self.engine = engine
        self.policy = window_policy or StaticWindowPolicy(4)
        self.cfg = cfg or ServerConfig()
        self.queue: list[ServeRequest] = []
        self.results: list[ServeResult] = []

    def submit(self, req: ServeRequest) -> None:
        self.queue.append(req)

    def _next_wave(self, arrived: list[ServeRequest]) -> list[ServeRequest]:
        head = arrived.pop(0)
        wave = [head]
        if self.cfg.length_aware:
            rest = sorted(arrived,
                          key=lambda r: abs(len(r.prompt) - len(head.prompt)))
            chosen = rest[: self.cfg.max_batch - 1]
            ids = {id(c) for c in chosen}
            arrived[:] = [r for r in arrived if id(r) not in ids]
            wave.extend(chosen)
        else:
            while arrived and len(wave) < self.cfg.max_batch:
                wave.append(arrived.pop(0))
        return wave

    def _pad_prompts(self, wave: list[ServeRequest]
                     ) -> tuple[np.ndarray, np.ndarray]:
        """RIGHT-pad to the wave max (rounded to pad_to). Right padding is
        exact here: attention pads are overwritten before any query can see
        them (kvcache pos_map induction) and SSM state is identity-masked
        past each sequence's true length."""
        q = self.cfg.pad_to
        maxlen = max(len(r.prompt) for r in wave)
        maxlen = ((maxlen + q - 1) // q) * q
        out = np.zeros((len(wave), maxlen), np.int32)
        lens = np.zeros(len(wave), np.int32)
        for i, r in enumerate(wave):
            out[i, :len(r.prompt)] = r.prompt
            lens[i] = len(r.prompt)
        return out, lens

    def run(self) -> list[ServeResult]:
        """Drain the queue wave by wave; returns per-request results."""
        pending = sorted(self.queue, key=lambda r: r.arrival_s)
        self.queue = []
        clock = _ArrivalClock()
        while pending:
            now = clock.now()
            arrived = [r for r in pending if r.arrival_s <= now]
            if not arrived:
                clock.wait_until(min(r.arrival_s for r in pending))
                continue
            wave = self._next_wave(arrived)
            for r in wave:
                pending.remove(r)
            prompts, lens = self._pad_prompts(wave)
            max_new = max(r.max_new_tokens for r in wave)
            wave_start = clock.now()
            assert self.cfg.transport is None, \
                "transports need the continuous server"
            tokens, stats = self.engine.generate(
                prompts, max_new, window_policy=self.policy,
                prompt_lens=lens, eos_id=self.cfg.eos_id,
                mode_policy=self.cfg.mode_policy)
            wave_end = clock.now()
            # wave-level timing attribution: the measured prefill wall time
            # IS the first-token time for every wave member (the anchor
            # token is sampled at the end of the batched prefill); decode
            # time spreads per produced token. Queue wait — arrival to the
            # wave's prefill — is part of every member's TTFT.
            first_tok_s = wave_start + stats.prefill_s
            for i, r in enumerate(wave):
                n = min(r.max_new_tokens, int(stats.produced[i]))
                seq_bits = stats.acceptance_seqs[i]
                acc = (sum(seq_bits) / len(seq_bits)) if seq_bits else 0.0
                self.results.append(ServeResult(
                    request_id=r.request_id,
                    tokens=tokens[i, :n],
                    ttft_ms=(first_tok_s - r.arrival_s) * 1e3,
                    tpot_ms=(wave_end - first_tok_s) * 1e3 / max(1, n - 1),
                    e2e_ms=(wave_end - r.arrival_s) * 1e3,
                    acceptance_rate=acc,
                    queue_ms=(wave_start - r.arrival_s) * 1e3))
        return self.results
