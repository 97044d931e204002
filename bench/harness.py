"""Build a cell's deployment, warm it up, and drive its measured window.

The window drives the system's own entry: ``SpecDecodeServer.run`` over a
deployment that ``repro.topology.build_deployment`` builds from a
``ClusterSpec``, as ``repro.launch.serve`` builds it (AWC window policy,
``mode_policy`` auto, ``sync_every`` 8, γ_max 12). The benchmark hands it
the weights it made from the seed (:mod:`bench.weights`) and the requests
its generator made (:mod:`bench.traffic`).

:class:`Probe` wraps four calls of each decode session from outside the
program: ``admit`` (the prefill-insert), ``run_chunk``, ``retire`` and
``_decide`` (the window policy's decision). It keeps host spans on the
host clock (and, in a traced run, ``TraceAnnotation`` spans in the
profiler's trace) of admissions and retirements, counts target passes,
committed tokens and decided windows per chunk, and ends the window: a backlog at the first chunk
boundary past ``--seconds``, an open loop once every request has retired
(or, if that never comes, ``DRAIN_LIMIT_S`` later).
"""

from __future__ import annotations

import dataclasses
import gc
import time
from contextlib import nullcontext
from typing import Optional

import numpy as np

from bench import traffic, weights
from bench.spec import Cell, family_of

DRAIN_LIMIT_S = 120.0     # an open loop's last request must retire by then
WARMUP_LIMIT_S = 900.0    # warm-up serving, compiles included, ends by then


class WindowClosed(Exception):
    """Raised out of ``run_chunk`` to end a window at a chunk boundary."""


@dataclasses.dataclass
class Chunk:
    t0: float
    t1: float
    passes: int
    tokens: int              # committed in the chunk (anchors excluded)
    active: int              # occupied, unfinished slots at its start
    contexts: list           # their live positions at its start
    gammas: list             # decided γ per pass (0 = fused)


class Probe:
    """Spans and counters around a session's calls, from outside."""

    def __init__(self, prompt_len: dict, trace: bool = False):
        self.prompt_len = prompt_len      # request id -> prompt length
        self.trace = trace
        self.chunks: list[Chunk] = []
        self.admits: list[tuple[int, float, float]] = []
        self.retired: dict[int, tuple[float, int]] = {}   # id -> (t, tokens)
        self.deadline: Optional[float] = None
        self.drain_deadline: Optional[float] = None
        self.closed_at: Optional[float] = None
        self._gammas: list[int] = []

    def _span(self, name: str):
        if self.trace:
            import jax
            return jax.profiler.TraceAnnotation(f"bench.{name}")
        return nullcontext()

    def attach(self, sess) -> None:
        admit, run_chunk = sess.admit, sess.run_chunk
        retire, decide = sess.retire, sess._decide

        def admit_(prompt, max_new, request_id=0, **kw):
            t0 = time.perf_counter()
            with self._span("admit"):
                out = admit(prompt, max_new, request_id=request_id, **kw)
            self.admits.append((request_id, t0, time.perf_counter()))
            return out

        def decide_(policy, q_depth):
            with self._span("decide"):
                gamma, fused = decide(policy, q_depth)
            self._gammas.append(0 if fused else int(gamma))
            return gamma, fused

        def run_chunk_(policy, max_iters=None, q_depth=0.0):
            live = [(j, sess.record(j)) for j in sess.occupied
                    if not sess.record(j).done]
            before = {j: r.produced for j, r in live}
            contexts = [self.prompt_len[r.request_id] + r.produced
                        for _, r in live]
            it0 = sess.iterations
            self._gammas = []
            t0 = time.perf_counter()
            with self._span("run_chunk"):
                n = run_chunk(policy, max_iters=max_iters, q_depth=q_depth)
            t1 = time.perf_counter()
            tokens = sum(r.produced - before[j] for j, r in live)
            self.chunks.append(Chunk(t0, t1, sess.iterations - it0, tokens,
                                     len(live), contexts,
                                     list(self._gammas)))
            if self.deadline is not None and t1 >= self.deadline:
                self.closed_at = t1
                raise WindowClosed()
            if self.drain_deadline is not None and t1 >= self.drain_deadline:
                self.closed_at = t1
                raise WindowClosed()
            return n

        def retire_(slot, scrub=False):
            with self._span("retire"):
                out = retire(slot, scrub=scrub)
            tokens, rec = out
            self.retired[rec.request_id] = (time.perf_counter(), len(tokens))
            return out

        sess.admit, sess.run_chunk = admit_, run_chunk_
        sess.retire, sess._decide = retire_, decide_


# -- deployment -----------------------------------------------------------

def cluster_spec(cell: Cell, seed: int, max_prompt: int, max_new: int):
    from repro.topology import one_pair_spec
    sv = cell.serving
    link = cell.traffic.get("link")
    spec = one_pair_spec(
        target=f"bench-{cell.target.name}",
        draft=f"bench-{cell.draft.name}",
        policy=sv["window_policy"], gamma_max=sv["gamma_max"],
        max_batch=sv["slots"], sync_every=sv["sync_every"],
        temperature=0.0, rtt_ms=sv["rtt_ms"],
        link_rtt_ms=None if link is None else link["rtt_ms"],
        link_jitter_ms=1.0 if link is None else link["jitter_ms"],
        link_bw_gbps=1.0 if link is None else link["bandwidth_gbps"],
        mode_policy=sv["mode_policy"], seed=seed % 2 ** 31)
    spec.full_width = True
    spec.serving.max_prompt_len = max_prompt
    spec.serving.max_new_cap = max_new
    return spec.validate()


@dataclasses.dataclass
class Built:
    deployment: object
    requests: list
    max_prompt: int
    max_new: int


def build(cell: Cell, seed: int, seconds: float) -> Built:
    """The configuration's weights (one jitted call per model, from its
    fixed ``weights_seed``), the deployment, and the run's requests (from
    ``seed``)."""
    from repro.topology import build_deployment
    max_prompt, max_new = traffic.max_lengths(cell.traffic, seconds)
    pad = int(cell.serving.get("pad_to", 16))
    max_prompt = -(-max_prompt // pad) * pad
    ws = int(cell.config["weights_seed"])
    tp = weights.program_params(cell.target, ws, 0)
    dp = tp if cell.self_draft else weights.program_params(cell.draft, ws, 1)
    spec = cluster_spec(cell, seed, max_prompt, max_new)
    configs = {f"bench-{m.name}": family_of(m).program_config(m)
               for m in (cell.target, cell.draft)}
    dep = build_deployment(spec, model_configs=configs,
                           node_params={"edge0": dp, "cloud0": tp})
    reqs = traffic.generate(cell.traffic, seed, seconds, cell.target.vocab)
    return Built(dep, reqs, max_prompt, max_new)


def serve_requests(reqs):
    from repro.serving import ServeRequest
    return [ServeRequest(r.request_id, r.prompt, r.max_new_tokens,
                         arrival_s=r.arrival_s) for r in reqs]


def probed_server(dep, probe: Probe, reqs):
    """The deployment's server, its sessions wrapped by ``probe`` as the
    serve loop makes them, with ``reqs`` submitted."""
    server = dep.build_server()
    make = server._make_session

    def make_session(pair, pending):
        sess = make(pair, pending)
        probe.attach(sess)
        return sess

    server._make_session = make_session
    for r in serve_requests(reqs):
        server.submit(r)
    return server


def fresh_policy(dep) -> None:
    from repro.core.window import make_window_policy
    for p, ps in zip(dep.pairs, dep.spec.pairs):
        w = ps.window
        p.policy = make_window_policy(w.kind, gamma=w.gamma, hi=w.hi,
                                      lo=w.lo, gmax=w.gmax)
        p.session = None


def warm_up(built: Built, cell: Cell) -> None:
    """Compile and run every program the window uses, at its shapes: the
    prefill-insert, the decode step (both modes over a transport), and
    retirement; then drop the warm-up sessions and give each pair a
    fresh window policy, so every window starts alike."""
    dep = built.deployment
    n = 2
    reqs = [traffic.Request(i, np.arange(built.max_prompt - i, dtype=np.int32)
                            % cell.target.vocab,
                            cell.serving["sync_every"] + 2, 0.0)
            for i in range(n)]
    modes = ["distributed", "fused"] if cell.traffic.get("link") else \
        [cell.serving["mode_policy"]]
    for mode in modes:
        for p in dep.pairs:
            p.mode_policy = mode
        probe = Probe({r.request_id: len(r.prompt) for r in reqs})
        probe.drain_deadline = time.perf_counter() + WARMUP_LIMIT_S
        server = probed_server(dep, probe, reqs)
        try:
            server.run()
        except WindowClosed:
            pass        # a stalled step: the window will show it
        del server
        fresh_policy(dep)
        gc.collect()
    for p in dep.pairs:
        p.mode_policy = cell.serving["mode_policy"]
    # a transport chunk that ends early slices its per-round stat rows to
    # the rounds it ran: one small program per length
    import jax.numpy as jnp
    rows = jnp.zeros((cell.serving["sync_every"], cell.serving["slots"]),
                     jnp.int32)
    for k in range(1, cell.serving["sync_every"] + 1):
        np.asarray(rows[:k])


@dataclasses.dataclass
class Window:
    results: list            # ServeResult of every retired request
    probe: Probe
    t0: float                # the serve loop's start (host clock)
    t1: float                # the window's end
    closed: bool             # ended at a chunk boundary, work in flight
    compiles: int            # XLA compiles inside the window
    in_flight: dict          # request id -> committed tokens, at the end


def run_window(built: Built, cell: Cell, seconds: float,
               trace_dir: Optional[str] = None) -> Window:
    import jax

    from repro.analysis.sanitize import compile_guard
    dep = built.deployment
    for p in dep.pairs:
        p.session = None          # one session's caches on the chip at once
    gc.collect()
    prompt_len = {r.request_id: len(r.prompt) for r in built.requests}
    probe = Probe(prompt_len, trace=trace_dir is not None)
    server = probed_server(dep, probe, built.requests)
    ctx = jax.profiler.trace(trace_dir) if trace_dir else nullcontext()
    closed = False
    with ctx:
        with probe._span("window"):
            t0 = time.perf_counter()
            if cell.traffic["loop"] == "backlog":
                probe.deadline = t0 + seconds
            else:
                probe.drain_deadline = t0 + seconds + DRAIN_LIMIT_S
            with compile_guard(allowed=None, what="measured window") as g:
                try:
                    server.run()
                except WindowClosed:
                    closed = True
            t1 = probe.closed_at if closed else time.perf_counter()
    in_flight = {}
    for sess in server._sessions:
        for j in sess.occupied:
            rec = sess.record(j)
            in_flight[rec.request_id] = rec.produced
    return Window(list(server.results), probe, t0, t1, closed,
                  g.backend_compiles, in_flight)


def free(built: Built) -> None:
    """Drop the program's state so the reference has the chip."""
    for p in built.deployment.pairs:
        p.session = None
        p.engine = None
    built.deployment = None
    gc.collect()
