"""Distributed draft–target execution tests.

The invariants: routing speculation rounds through the zero-delay
:class:`InProcessTransport` commits greedy tokens BIT-identical to the
colocated ``DecodeSession`` path (dense, SSM and hybrid targets — the
regression anchor for the worker split); the
:class:`EmulatedLinkTransport` imposes measured wall-clock delays sampled
from the same ``LinkSpec`` model DSD-Sim uses and feeds the MEASURED RTT
into the window-policy features (so AWC flips to fused mode on a slow
link); and fused-mode rounds commit exactly the target's greedy
continuation while paying no per-window round trips.
"""

import time

import jax
import numpy as np
import pytest

from repro.core.engine import SpecDecodeEngine
from repro.core.session import DecodeSession
from repro.core.window import AWCWindowPolicy, StaticWindowPolicy
from repro.distributed import (EmulatedLinkTransport, InProcessTransport,
                               VerdictMsg, WindowMsg)
from repro.sim.network import (LinkSpec, verdict_payload_bytes,
                               window_payload_bytes)

# model pairs / γ / engine builder come from the shared conformance
# fixture module (one definition for every distributed/session test)
from conformance.scenarios import DRAFT, GAMMA, TARGETS, make_engine

_engine = make_engine


def _prompts(rng, n, lo=6, hi=12):
    return [rng.integers(0, 128, int(rng.integers(lo, hi))).astype(np.int32)
            for _ in range(n)]


# ------------------------------------------------------- bit-identity anchor

@pytest.mark.parametrize("family", [
    "dense",
    pytest.param("ssm", marks=pytest.mark.slow),
    pytest.param("hybrid", marks=pytest.mark.slow),
])
def test_inprocess_transport_bit_identical(family):
    """Greedy tokens through the split-worker + InProcessTransport path ==
    the colocated fused-step path, for attention AND recurrent targets."""
    eng = _engine(family)
    rng = np.random.default_rng(3)
    prompts = rng.integers(0, 128, (2, 9)).astype(np.int32)
    ref, ref_stats = eng.generate(prompts, 12, StaticWindowPolicy(GAMMA))
    got, got_stats = eng.generate(prompts, 12, StaticWindowPolicy(GAMMA),
                                  transport=InProcessTransport())
    np.testing.assert_array_equal(ref, got)
    assert ref_stats.accepted == got_stats.accepted
    assert ref_stats.proposed == got_stats.proposed


def test_inprocess_transport_staggered_admission():
    """In-flight admission/retirement through the transport path commits
    the same greedy tokens as solo colocated runs."""
    eng = _engine("dense")
    rng = np.random.default_rng(5)
    prompts = _prompts(rng, 3)
    pol = StaticWindowPolicy(GAMMA)
    sess = DecodeSession(eng, capacity=2, max_new_cap=8, max_prompt_len=16,
                         gamma_max=GAMMA, sync_every=2,
                         transport=InProcessTransport())
    outs = {}
    sess.admit(prompts[0], 8, request_id=0)
    sess.run_chunk(pol)
    sess.admit(prompts[1], 6, request_id=1)
    for _ in range(64):
        if not sess.unfinished:
            break
        sess.run_chunk(pol)
        for j in sess.finished_slots():
            toks, rec = sess.retire(j)
            outs[rec.request_id] = toks
            if rec.request_id == 0 and 2 not in outs:
                sess.admit(prompts[2], 8, request_id=2)
                outs[2] = None
    assert not sess.unfinished
    for j in sess.finished_slots():
        toks, rec = sess.retire(j)
        outs[rec.request_id] = toks
    budgets = {0: 8, 1: 6, 2: 8}
    for rid, p in enumerate(prompts):
        solo, _ = eng.generate(p[None, :], budgets[rid],
                               StaticWindowPolicy(GAMMA))
        np.testing.assert_array_equal(outs[rid], solo[0, :budgets[rid]])


def test_transport_zero_recompiles_across_churn():
    """The distributed programs (propose + verify/commit + insert) compile
    once; admissions, retirements and γ changes are data."""
    eng = _engine("dense")
    rng = np.random.default_rng(1)
    pol = StaticWindowPolicy(GAMMA)
    sess = DecodeSession(eng, capacity=2, max_new_cap=6, max_prompt_len=12,
                         gamma_max=GAMMA, sync_every=2,
                         transport=InProcessTransport())
    sess.admit(rng.integers(0, 128, 7).astype(np.int32), 6, request_id=0)
    sess.run_chunk(pol)
    warm = eng.compiled_programs()
    outs = {}
    for rid in range(1, 4):
        sess.admit(rng.integers(0, 128, int(rng.integers(2, 12)))
                   .astype(np.int32), int(rng.integers(2, 7)),
                   request_id=rid)
        while not sess.free:
            sess.run_chunk(pol)
            for j in sess.finished_slots():
                toks, rec = sess.retire(j)
                outs[rec.request_id] = toks
    while sess.unfinished:
        sess.run_chunk(pol)
    assert eng.compiled_programs() == warm


# ----------------------------------------------------------- fused execution

def test_fused_mode_commits_target_greedy():
    """Forced fused mode (cloud-only) produces exactly the target's greedy
    continuation — the same committed stream as greedy speculative
    decoding — through the transport, with zero window/verdict messages."""
    eng = _engine("dense")
    rng = np.random.default_rng(2)
    prompts = rng.integers(0, 128, (2, 8)).astype(np.int32)
    ref, _ = eng.generate(prompts, 10, StaticWindowPolicy(GAMMA))
    tr = InProcessTransport()
    fus, stats = eng.generate(prompts, 10, StaticWindowPolicy(GAMMA),
                              transport=tr, mode_policy="fused")
    np.testing.assert_array_equal(ref, fus)
    assert stats.proposed == 0            # no speculation in fused mode
    # only per-chunk control flushes crossed the wire, never a window
    assert tr.bytes_sent < 64 * stats.iterations


def test_fused_mode_colocated_matches_greedy():
    """The colocated path honors fused decisions too (γ=0 masked step)."""
    eng = _engine("ssm")
    rng = np.random.default_rng(4)
    prompts = rng.integers(0, 128, (2, 8)).astype(np.int32)
    ref, _ = eng.generate(prompts, 10, StaticWindowPolicy(GAMMA))
    fus, stats = eng.generate(prompts, 10, StaticWindowPolicy(GAMMA),
                              mode_policy="fused")
    np.testing.assert_array_equal(ref, fus)
    assert stats.proposed == 0


def test_mixed_mode_switching_stays_greedy():
    """Alternating fused/distributed decisions mid-stream (the draft cache
    must stay coherent across fused rounds) still commits the target's
    greedy continuation."""

    class Alternator:
        def __init__(self):
            self.i = 0

        def decide(self, pair_key, feats):
            from repro.core.window import WindowDecision
            self.i += 1
            if (self.i // 3) % 2 == 1:
                return WindowDecision(1, "fused")
            return WindowDecision(GAMMA, "distributed")

        def gamma_bound(self):
            return GAMMA

        def name(self):
            return "alternator"

    eng = _engine("dense")
    rng = np.random.default_rng(6)
    prompts = rng.integers(0, 128, (2, 9)).astype(np.int32)
    ref, _ = eng.generate(prompts, 12, StaticWindowPolicy(GAMMA))
    got, stats = eng.generate(prompts, 12, Alternator(),
                              transport=InProcessTransport())
    np.testing.assert_array_equal(ref, got)
    assert stats.proposed > 0             # some distributed rounds ran


# ------------------------------------------------------------- emulated link

def _msgs(rid=0, gamma=4, speculative=False):
    w = WindowMsg(tokens=np.zeros((1, gamma), np.int32), gamma=gamma,
                  n_active=1, round_id=rid, speculative=speculative)
    v = VerdictMsg(n_accepted=np.zeros(1, np.int32),
                   num_new=np.ones(1, np.int32),
                   next_token=np.zeros(1, np.int32),
                   last_token=np.zeros(1, np.int32),
                   done=np.zeros(1, bool), gamma=gamma, n_active=1,
                   round_id=rid)
    return w, v


def test_emulated_link_records_sampled_delays():
    """The transport's RECORDED delay samples (not wall-clock sleeps — the
    deflaked contract) follow the LinkSpec model: per-direction logs, RTT
    pairs reconstructed from the sampled out+back sums, byte accounting
    per the paper's payload model. Seeded jitter makes this exact."""
    spec = LinkSpec(rtt_ms=20.0, jitter_ms=1.0)
    tr = EmulatedLinkTransport(spec, seed=0, sleep=False)
    for i in range(4):
        w, v = _msgs(rid=i)
        tr.send_window(w)
        tr.send_verdict(v)
    assert len(tr.delay_log["window"]) == 4
    assert len(tr.delay_log["verdict"]) == 4
    # sampled one-way delays respect the truncated-jitter bounds
    for d in tr.delay_log["window"] + tr.delay_log["verdict"]:
        assert 0.0 < d <= 0.5 * spec.rtt_ms + 4.0 * spec.jitter_ms + 1.0
    pairs = [o + b for o, b in zip(tr.delay_log["window"],
                                   tr.delay_log["verdict"])]
    assert tr.recent_rtt_ms == pytest.approx(sum(pairs) / len(pairs))
    assert tr.bytes_sent == 4 * (window_payload_bytes(4)
                                 + verdict_payload_bytes(4))
    assert tr.messages_sent == 8


def test_emulated_link_sleep_blocks_at_least_the_samples():
    """The sleeping transport really blocks: elapsed wall time is bounded
    below by the recorded samples (sleeps can only overshoot, so this
    direction is robust under scheduler noise)."""
    tr = EmulatedLinkTransport(LinkSpec(rtt_ms=20.0, jitter_ms=1.0), seed=0)
    w, v = _msgs(rid=0)
    t0 = time.perf_counter()
    tr.send_window(w)
    tr.send_verdict(v)
    wall_ms = (time.perf_counter() - t0) * 1e3
    sampled = tr.delay_log["window"][0] + tr.delay_log["verdict"][0]
    assert wall_ms >= 0.9 * sampled


def test_rtt_pairing_by_round_id_out_of_order():
    """Pipelined completion scrambles delivery order: a speculative window
    for round k+1 is posted before round k's verdict. RTT pairs must match
    by round id, and a discarded (invalidated) window must never pair."""
    spec = LinkSpec(rtt_ms=10.0, jitter_ms=0.5)
    tr = EmulatedLinkTransport(spec, seed=3, sleep=False)
    w1, v1 = _msgs(rid=1)
    w2, v2 = _msgs(rid=2, speculative=True)
    tr.post_window(w1)
    tr.post_window(w2)                 # in flight before verdict 1
    tr.recv_window()
    tr.post_verdict(v1)
    tr.recv_verdict()
    tr.post_verdict(v2)
    tr.recv_window()
    tr.recv_verdict()
    d = tr.delay_log
    expect = [(d["window"][0] + d["verdict"][0]),
              (d["window"][1] + d["verdict"][1])]
    assert tr.recent_rtt_ms == pytest.approx(sum(expect) / 2)
    # a discarded speculative window clears its half-pair: the next
    # verdict carrying a NEW round id cannot mismatch it
    w3, _ = _msgs(rid=3, speculative=True)
    tr.post_window(w3)
    dropped = tr.discard_window()
    assert dropped.round_id == 3 and tr.discarded_messages == 1
    w4, v4 = _msgs(rid=4)
    tr.post_window(w4)
    tr.recv_window()
    tr.post_verdict(v4)
    tr.recv_verdict()
    assert tr.recent_rtt_ms == pytest.approx(
        (expect[0] + expect[1] + d["window"][3] + d["verdict"][2]) / 3)


def test_emulated_link_rtt_feeds_policy_and_flips_fused():
    """The AWC feature loop closes over the transport: the SAME
    rtt-sensitive predictor keeps γ large through a zero-delay transport
    and flips to fused over a 20 ms emulated link, because
    ``rtt_recent_ms`` now comes from the transport's measurements."""
    def predictor(feats):
        return 1.0 if feats[2] > 10.0 else 6.0       # feats[2] = rtt_recent

    rng = np.random.default_rng(3)
    prompts = rng.integers(0, 128, (2, 9)).astype(np.int32)
    ref = None
    for name, make_tr in [
            ("inproc", InProcessTransport),
            ("rtt20", lambda: EmulatedLinkTransport(
                LinkSpec(rtt_ms=20.0, jitter_ms=1.0), seed=0))]:
        eng = _engine("dense")
        tr = make_tr()
        sess = DecodeSession(eng, capacity=2, max_new_cap=10, gamma_max=6,
                             sync_every=2, transport=tr)
        sess.admit_batch(prompts, 10)
        pol = AWCWindowPolicy(predictor)
        while sess.unfinished and sess.iterations < 40:
            sess.run_chunk(pol)
        toks, stats = sess.snapshot()
        if name == "inproc":
            assert sess.fused_iterations == 0
            assert max(stats.gamma_seq) == 6
            ref = toks
        else:
            assert sess.fused_iterations > 0          # flipped to fused
            assert tr.recent_rtt_ms > 10.0            # measured, not default
            # greedy commits are mode-invariant: same tokens either way
            np.testing.assert_array_equal(ref, toks)


def test_session_link_accounting():
    """Per-session link accounting: imposed delay accumulates in link_ms
    and the TPOT feature excludes it."""
    eng = _engine("dense")
    rng = np.random.default_rng(8)
    prompts = rng.integers(0, 128, (2, 8)).astype(np.int32)
    tr = EmulatedLinkTransport(LinkSpec(rtt_ms=10.0, jitter_ms=0.5), seed=1)
    sess = DecodeSession(eng, capacity=2, max_new_cap=6, gamma_max=GAMMA,
                         sync_every=2, transport=tr)
    sess.admit_batch(prompts, 6)
    while sess.unfinished and sess.iterations < 24:
        sess.run_chunk(StaticWindowPolicy(GAMMA))
    assert sess.link_ms > 0.0
    feats = sess._features(0.0)
    # tpot tracks target service time; the link delay (≥ rtt_ms per round)
    # stays out of it, so per-iteration tpot < per-iteration wall time
    assert feats.tpot_recent_ms < \
        sess.decode_wall_s * 1e3 / max(1, sess.iterations)
    assert feats.rtt_recent_ms == tr.recent_rtt_ms


def test_sampled_transport_distributed_and_fused_rounds():
    """Temperature > 0 exercises the q_probs-carrying verify signature
    (distributed rounds ship draft distributions; fused rounds use the
    cached zero placeholder) — the wire path must produce valid tokens
    and speculation stats in both modes."""
    eng = SpecDecodeEngine(DRAFT, TARGETS["dense"], temperature=1.0,
                           key=jax.random.PRNGKey(7))
    rng = np.random.default_rng(11)
    prompts = rng.integers(0, 128, (2, 8)).astype(np.int32)
    toks, stats = eng.generate(prompts, 8, StaticWindowPolicy(GAMMA),
                               transport=InProcessTransport())
    assert (toks[:, :8] >= 0).all() and stats.proposed > 0
    fus, fstats = eng.generate(prompts, 8, StaticWindowPolicy(GAMMA),
                               transport=InProcessTransport(),
                               mode_policy="fused")
    assert (fus[:, :8] >= 0).all() and fstats.proposed == 0


def test_non_sleeping_transport_keeps_tpot_honest():
    """With sleep=False the sampled delay never entered wall time, so it
    must NOT be subtracted from the TPOT feature (which would clamp it to
    ~0) — it lands on the virtual clock instead."""
    eng = _engine("dense")
    rng = np.random.default_rng(9)
    prompts = rng.integers(0, 128, (2, 8)).astype(np.int32)
    # warm the split-worker programs (same buffer geometry: max_new and
    # sync_every shape the stats buffers) so compile stays out of wall
    eng.generate(prompts, 6, StaticWindowPolicy(GAMMA), sync_every=2,
                 transport=InProcessTransport())
    tr = EmulatedLinkTransport(LinkSpec(rtt_ms=80.0, jitter_ms=0.5),
                               seed=1, sleep=False)
    sess = DecodeSession(eng, capacity=2, max_new_cap=6, gamma_max=GAMMA,
                         sync_every=2, transport=tr)
    sess.admit_batch(prompts, 6)
    t0 = time.perf_counter()
    while sess.unfinished and sess.iterations < 24:
        sess.run_chunk(StaticWindowPolicy(GAMMA))
    wall_ms = (time.perf_counter() - t0) * 1e3
    assert sess.link_ms > 80.0           # sampled delays were charged...
    assert wall_ms < sess.link_ms        # ...but never slept
    assert sess.virtual_ms >= sess.link_ms   # they hit the virtual clock
    feats = sess._features(0.0)
    assert feats.tpot_recent_ms > 0.0    # not clamped to zero by link_ms


# ------------------------------------------------- socket transport parity

def test_socket_loopback_bit_identical():
    """Greedy tokens through the TCP-loopback SocketTransport — every
    window/verdict length-prefix framed through the kernel — match the
    colocated path token for token."""
    from repro.distributed import SocketTransport
    eng = _engine("dense")
    rng = np.random.default_rng(3)
    prompts = rng.integers(0, 128, (2, 9)).astype(np.int32)
    ref, ref_stats = eng.generate(prompts, 12, StaticWindowPolicy(GAMMA))
    tr = SocketTransport.loopback()
    try:
        got, got_stats = eng.generate(prompts, 12, StaticWindowPolicy(GAMMA),
                                      transport=tr)
        np.testing.assert_array_equal(ref, got)
        assert ref_stats.accepted == got_stats.accepted
        assert tr.wire_bytes > 0 and tr.in_flight == 0
    finally:
        tr.close()


@pytest.mark.slow
def test_process_hosts_match_in_process(tmp_path):
    """The full multi-process path: draft and target worker hosts in
    their own interpreters, windows/verdicts over two TCP streams — the
    committed greedy tokens must equal the same spec served in process."""
    import dataclasses

    from repro.serving import ServeRequest
    from repro.topology import (ClusterSpec, NodeSpec, PairSpec, ServingSpec,
                                WindowSpec, WorkloadSpec, build_deployment)
    cfgs = {"d": DRAFT, "t": TARGETS["dense"]}
    spec = ClusterSpec(
        nodes=[NodeSpec(id="edge0", role="draft", model="d"),
               NodeSpec(id="cloud0", role="target", model="t")],
        pairs=[PairSpec(id="pair0", draft="edge0", target="cloud0",
                        window=WindowSpec(kind="static", gamma=GAMMA),
                        mode_policy="distributed", process=True)],
        serving=ServingSpec(max_batch=2, sync_every=2, gamma_max=GAMMA,
                            temperature=0.0, server="continuous",
                            max_new_cap=8),
        workload=WorkloadSpec(num_requests=2, max_new=8),
        seed=11)
    rng = np.random.default_rng(0)
    reqs = [(rid, rng.integers(0, 128, 7).astype(np.int32))
            for rid in range(2)]

    def serve(s):
        dep = build_deployment(s, model_configs=cfgs)
        try:
            srv = dep.build_server()
            for rid, prompt in reqs:
                srv.submit(ServeRequest(rid, prompt, 8))
            res = {r.request_id: r.tokens for r in srv.run()}
            return res, srv.pair_summaries()
        finally:
            dep.shutdown()

    got, ps = serve(spec)
    ref, _ = serve(dataclasses.replace(
        spec, pairs=[dataclasses.replace(spec.pairs[0], process=False)]))
    assert set(got) == set(ref) == {0, 1}
    for rid in ref:
        np.testing.assert_array_equal(got[rid], ref[rid])
    row = ps["pair0"]
    assert row["process"] is True and row["wire_bytes"] > 0


def test_process_pair_spec_validation():
    """process: true is restricted to the cross-process-deterministic
    regime — greedy, distributed mode, static window, continuous server —
    and rejected loudly otherwise."""
    import dataclasses

    from repro.topology import (ClusterSpec, NodeSpec, PairSpec, ServingSpec,
                                TopologyError, WindowSpec, WorkloadSpec)
    base = ClusterSpec(
        nodes=[NodeSpec(id="e", role="draft", model="d"),
               NodeSpec(id="c", role="target", model="t")],
        pairs=[PairSpec(id="p", draft="e", target="c",
                        window=WindowSpec(kind="static", gamma=3),
                        mode_policy="distributed", process=True)],
        serving=ServingSpec(max_batch=1, server="continuous",
                            temperature=0.0),
        workload=WorkloadSpec(num_requests=1, max_new=4))
    base.validate()
    for mutate, msg in [
            (lambda s: setattr(s.serving, "temperature", 0.7), "temperature"),
            (lambda s: s.pairs.__setitem__(0, dataclasses.replace(
                s.pairs[0], mode_policy="auto")), "mode_policy"),
            (lambda s: s.pairs.__setitem__(0, dataclasses.replace(
                s.pairs[0], window=WindowSpec(kind="awc", gamma=3))),
             "window"),
            (lambda s: setattr(s.serving, "server", "legacy"), "continuous"),
            (lambda s: s.nodes.__setitem__(0, dataclasses.replace(
                s.nodes[0], port=99999)), "port")]:
        spec = ClusterSpec.from_dict(base.to_dict())
        mutate(spec)
        with pytest.raises(TopologyError, match=msg):
            spec.validate()


@pytest.mark.parametrize("holds,chips,msg", [
    (True, 4, "already holds the TPU"),
    (False, 1, "this host has 1"),
], ids=["caller-holds-tpu", "too-few-chips"])
def test_spawn_pair_refuses_at_once_on_a_tpu_host(monkeypatch, holds, chips,
                                                  msg):
    """One process per chip: on a TPU host, spawn_pair refuses before any
    worker starts when this process already holds the TPU or the host has
    fewer chips than worker processes — it never waits out the handshake.
    The elastic pool and build_deployment go through the same check."""
    import subprocess

    import repro.distributed.host as host
    from repro.fleet.elastic import ElasticPairPool
    from repro.topology import (ClusterSpec, NodeSpec, PairSpec, ServingSpec,
                                TopologyError, WindowSpec, WorkloadSpec,
                                build_deployment)
    monkeypatch.setattr(host, "holds_tpu", lambda: holds)
    monkeypatch.setattr(host, "host_tpu_chips", lambda: chips)

    def no_spawn(*a, **k):
        raise AssertionError("a worker process was started")

    monkeypatch.setattr(subprocess, "Popen", no_spawn)
    spec = ClusterSpec(
        nodes=[NodeSpec(id="e", role="draft", model="d"),
               NodeSpec(id="c", role="target", model="t")],
        pairs=[PairSpec(id="p", draft="e", target="c",
                        window=WindowSpec(kind="static", gamma=3),
                        mode_policy="distributed", process=True)],
        serving=ServingSpec(max_batch=1, server="continuous",
                            temperature=0.0),
        workload=WorkloadSpec(num_requests=1, max_new=4))
    cfgs = {"d": DRAFT, "t": TARGETS["dense"]}
    with pytest.raises(TopologyError, match="one process per chip"):
        host.spawn_pair(spec, spec.pairs[0], model_configs=cfgs)
    with pytest.raises(TopologyError, match=msg):
        build_deployment(spec, model_configs=cfgs)
    with pytest.raises(TopologyError, match="one process per chip"):
        ElasticPairPool(spec, model_configs=cfgs).scale_up()
