"""Topology spec layer: JSON round trip (hypothesis property), validate()
rejections, deployment structure, legacy-flag shim equivalence, and the
one-pair bit-identity regression (topology-built serving == the
pre-topology hand-wired server)."""

import dataclasses

import numpy as np
import pytest

import jax

from repro.configs.base import ModelConfig
from repro.core.engine import SpecDecodeEngine
from repro.core.window import StaticWindowPolicy
from repro.distributed import InProcessTransport
from repro.serving import (LeastLoadedPairRouter, ServeRequest, ServerConfig,
                           SpecDecodeServer)
from repro.sim.network import LinkSpec
from repro.topology import (ClusterSpec, NodeSpec, PairSpec, ServingSpec,
                            TopologyError, WindowSpec, WorkloadSpec,
                            build_deployment, build_simulation,
                            one_pair_spec)

TINY_T = ModelConfig(name="topo-t", arch_type="dense", n_layers=2,
                     d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
                     vocab=128, dtype="float32", remat=False)
TINY_D = dataclasses.replace(TINY_T, name="topo-d", n_layers=1)
TINY = {"topo-t": TINY_T, "topo-d": TINY_D}


def two_pair_spec(rtt_fast=0.0, rtt_slow=40.0, window=None,
                  max_batch=2) -> ClusterSpec:
    window = window or WindowSpec("static", 3)
    return ClusterSpec(
        nodes=[NodeSpec("e0", "draft", "topo-d"),
               NodeSpec("e1", "draft", "topo-d"),
               NodeSpec("c0", "target", "topo-t")],
        pairs=[PairSpec("fast", "e0", "c0",
                        link=LinkSpec(rtt_ms=rtt_fast, jitter_ms=0.0),
                        window=window),
               PairSpec("slow", "e1", "c0",
                        link=LinkSpec(rtt_ms=rtt_slow, jitter_ms=1.0),
                        window=window)],
        serving=ServingSpec(max_batch=max_batch, gamma_max=6, sync_every=4),
        workload=WorkloadSpec(num_requests=4, max_new=8))


# ----------------------------------------------------------- JSON round trip

def test_round_trip_explicit():
    spec = two_pair_spec()
    again = ClusterSpec.from_json(spec.to_json())
    assert again == spec
    # and None links / defaults survive too
    spec2 = one_pair_spec()
    assert ClusterSpec.from_json(spec2.to_json()) == spec2
    assert spec2.pairs[0].link is None


def test_from_dict_rejects_unknown_fields():
    d = two_pair_spec().to_dict()
    d["nodes"][0]["gpu_count"] = 9
    with pytest.raises(TopologyError):
        ClusterSpec.from_dict(d)


try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:             # pragma: no cover - CI installs hypothesis
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    _name = st.text(alphabet="abcdef012", min_size=1, max_size=6)
    _pos = st.floats(min_value=0.0, max_value=500.0, allow_nan=False)

    @st.composite
    def cluster_specs(draw):
        n_d = draw(st.integers(1, 3))
        n_t = draw(st.integers(1, 2))
        nodes = [NodeSpec(id=f"d{i}", role="draft", model=draw(_name),
                          device=draw(_name), hw=draw(_name),
                          sim_model=draw(_name),
                          tp=draw(st.integers(0, 8)))
                 for i in range(n_d)]
        nodes += [NodeSpec(id=f"t{i}", role="target", model=draw(_name))
                  for i in range(n_t)]
        pairs = []
        for i in range(draw(st.integers(1, 4))):
            has_link = draw(st.booleans())
            link = None
            if has_link:
                link = LinkSpec(rtt_ms=draw(_pos), jitter_ms=draw(_pos),
                                bandwidth_gbps=draw(st.floats(
                                    min_value=0.01, max_value=100.0,
                                    allow_nan=False)),
                                name=draw(_name))
            mode = draw(st.sampled_from(
                ("auto", "distributed", "fused", "pipeline") if has_link
                else ("auto", "distributed", "fused")))
            window = WindowSpec(
                kind=draw(st.sampled_from(("static", "dynamic", "awc"))),
                gamma=draw(st.integers(1, 12)), hi=draw(_pos),
                lo=draw(_pos), gmax=draw(st.integers(1, 16)))
            pairs.append(PairSpec(
                id=f"p{i}", draft=f"d{draw(st.integers(0, n_d - 1))}",
                target=f"t{draw(st.integers(0, n_t - 1))}", link=link,
                window=window, mode_policy=mode))
        serving = ServingSpec(max_batch=draw(st.integers(1, 16)),
                              length_aware=draw(st.booleans()),
                              sync_every=draw(st.integers(1, 16)),
                              gamma_max=draw(st.integers(2, 16)),
                              temperature=draw(st.floats(
                                  min_value=0.0, max_value=2.0,
                                  allow_nan=False)),
                              rtt_ms=draw(_pos),
                              router=draw(st.sampled_from(
                                  ("least-loaded", "round-robin"))))
        workload = WorkloadSpec(dataset=draw(_name),
                                num_requests=draw(st.integers(0, 64)),
                                max_new=draw(st.integers(1, 128)),
                                rate_per_s=draw(_pos),
                                prompt_lo=draw(st.integers(1, 8)),
                                prompt_hi=draw(st.integers(9, 64)))
        return ClusterSpec(nodes=nodes, pairs=pairs, serving=serving,
                           workload=workload,
                           seed=draw(st.integers(0, 2**31 - 1)))

    @settings(max_examples=60, deadline=None)
    @given(cluster_specs())
    def test_round_trip_property(spec):
        """spec == decode(encode(spec)) — exact, including floats, None
        links, and every nested dataclass — and generated specs pass
        validate()."""
        spec.validate()
        assert ClusterSpec.from_json(spec.to_json()) == spec
        # dict round trip too (the path the launcher file-loading uses)
        assert ClusterSpec.from_dict(spec.to_dict()) == spec


# --------------------------------------------------------------- validate()

def _valid() -> ClusterSpec:
    return two_pair_spec()


def test_validate_accepts_valid_spec():
    _valid().validate()


@pytest.mark.parametrize("mutate,msg", [
    (lambda s: s.pairs.__setitem__(
        0, dataclasses.replace(s.pairs[0], draft="ghost")),
     "unknown node ref"),
    (lambda s: s.pairs.__setitem__(
        1, dataclasses.replace(s.pairs[1], id="fast")),
     "duplicate pair id"),
    (lambda s: s.nodes.append(NodeSpec("e0", "draft", "topo-d")),
     "duplicate node id"),
    (lambda s: s.pairs.__setitem__(
        0, dataclasses.replace(s.pairs[0],
                               link=LinkSpec(rtt_ms=-5.0))),
     "negative rtt_ms"),
    (lambda s: s.pairs.__setitem__(
        0, dataclasses.replace(s.pairs[0],
                               link=LinkSpec(rtt_ms=1.0, jitter_ms=-1.0))),
     "negative jitter_ms"),
    (lambda s: s.pairs.__setitem__(
        0, dataclasses.replace(
            s.pairs[0], link=LinkSpec(bandwidth_gbps=0.0))),
     "bandwidth_gbps"),
    (lambda s: s.nodes.__setitem__(
        2, dataclasses.replace(s.nodes[2], role="oracle")),
     "role"),
    (lambda s: s.pairs.__setitem__(
        0, dataclasses.replace(s.pairs[0], target="e1")),
     "role"),   # wrong-role reference: a draft node used as target
    (lambda s: s.pairs.__setitem__(
        0, dataclasses.replace(s.pairs[0], mode_policy="warp")),
     "mode_policy"),
    (lambda s: s.pairs.__setitem__(
        0, dataclasses.replace(s.pairs[0], link=None,
                               mode_policy="pipeline")),
     "pipeline"),
    (lambda s: s.pairs.__setitem__(
        0, dataclasses.replace(s.pairs[0],
                               window=WindowSpec(kind="prophet"))),
     "window kind"),
    (lambda s: s.pairs.__setitem__(
        0, dataclasses.replace(s.pairs[0],
                               window=WindowSpec(gamma=0))),
     "gamma"),
    (lambda s: setattr(s.serving, "max_batch", 0), "max_batch"),
    (lambda s: setattr(s.serving, "router", "psychic"), "router"),
    (lambda s: setattr(s.serving, "server", "wave"), "wave"),
    (lambda s: setattr(s.workload, "max_new", 0), "max_new"),
    # prompt_hi is an EXCLUSIVE bound (numpy integers semantics): an
    # empty range must be rejected at validate(), not crash the launcher
    (lambda s: (setattr(s.workload, "prompt_lo", 32),
                setattr(s.workload, "prompt_hi", 32)), "prompt_lo"),
    (lambda s: s.pairs.clear(), "at least one pair"),
])
def test_validate_rejections(mutate, msg):
    spec = _valid()
    mutate(spec)
    with pytest.raises(TopologyError, match=msg.split()[0]):
        spec.validate()


# ------------------------------------------------------- legacy-flag shim

def test_legacy_flags_compile_to_equivalent_one_pair_spec():
    """Every pre-existing launch.serve flag combination maps to a one-pair
    ClusterSpec — including --link-rtt-ms 0 (zero-delay in-process link)
    and --mode-policy pipeline."""
    spec = one_pair_spec(target="qwen3-14b", draft="qwen2.5-3b",
                         policy="awc", gamma=6, gamma_max=10, max_batch=3,
                         sync_every=4, temperature=0.5, rtt_ms=7.0,
                         link_rtt_ms=0.0, link_jitter_ms=2.0,
                         link_bw_gbps=0.5, mode_policy="pipeline",
                         requests=5, max_new=17, arrival_rate=3.0, seed=9)
    spec.validate()
    assert spec == ClusterSpec(
        nodes=[NodeSpec("edge0", "draft", "qwen2.5-3b"),
               NodeSpec("cloud0", "target", "qwen3-14b")],
        pairs=[PairSpec("pair0", "edge0", "cloud0",
                        link=LinkSpec(rtt_ms=0.0, jitter_ms=2.0,
                                      bandwidth_gbps=0.5),
                        window=WindowSpec(kind="awc", gamma=6),
                        mode_policy="pipeline")],
        serving=ServingSpec(max_batch=3, sync_every=4, gamma_max=10,
                            temperature=0.5, rtt_ms=7.0),
        workload=WorkloadSpec(num_requests=5, max_new=17, rate_per_s=3.0),
        seed=9)
    # no link flags -> colocated pair, no transport
    colocated = one_pair_spec(mode_policy="auto")
    assert colocated.pairs[0].link is None
    deployment = build_deployment(
        dataclasses.replace(colocated, nodes=[
            NodeSpec("edge0", "draft", "topo-d"),
            NodeSpec("cloud0", "target", "topo-t")]),
        model_configs=TINY)
    assert deployment.pairs[0].transport is None


# -------------------------------------------------- deployment structure

def test_build_deployment_shares_node_params_and_isolates_pairs():
    spec = two_pair_spec()
    dep = build_deployment(spec, model_configs=TINY, sleep_links=False)
    assert [p.pair_id for p in dep.pairs] == ["fast", "slow"]
    e_fast, e_slow = dep.pairs[0].engine, dep.pairs[1].engine
    # distinct draft nodes -> distinct engines, but ONE set of target
    # params built for the shared cloud node
    assert e_fast is not e_slow
    assert e_fast.target_params is e_slow.target_params
    assert e_fast.draft_params is not e_slow.draft_params
    # one transport and one policy instance per pair
    assert dep.pairs[0].transport is not dep.pairs[1].transport
    assert isinstance(dep.pairs[0].transport, InProcessTransport)
    assert type(dep.pairs[1].transport).__name__ == "EmulatedLinkTransport"
    assert dep.pairs[0].policy is not dep.pairs[1].policy
    assert isinstance(dep.router, LeastLoadedPairRouter)
    assert dep.vocab == TINY_T.vocab


def test_build_deployment_validates():
    spec = two_pair_spec()
    spec.pairs[1] = dataclasses.replace(spec.pairs[1], draft="ghost")
    with pytest.raises(TopologyError):
        build_deployment(spec, model_configs=TINY)


# ------------------------------------------------ one-pair bit identity

def test_topology_server_bit_identical_to_legacy_path():
    """A one-pair spec with a zero-delay link, built through
    build_deployment, must commit greedy tokens BIT-identical to the
    hand-wired engine + ServerConfig(transport=...) surface the launcher
    used before the topology API existed."""
    spec = one_pair_spec(target="topo-t", draft="topo-d", policy="static",
                         gamma=3, gamma_max=6, max_batch=2, sync_every=4,
                         temperature=0.0, link_rtt_ms=0.0, seed=3)
    dep = build_deployment(spec, model_configs=TINY)
    srv_topo = dep.build_server()

    # the legacy construction, byte for byte what launch.serve did pre-PR5
    engine = SpecDecodeEngine(TINY_D, TINY_T, temperature=0.0, rtt_ms=10.0,
                              gamma_max=6, sync_every=4,
                              key=jax.random.PRNGKey(3))
    srv_legacy = SpecDecodeServer(
        engine, StaticWindowPolicy(3),
        ServerConfig(max_batch=2, transport=InProcessTransport()))

    rng = np.random.default_rng(0)
    reqs = [(i, rng.integers(0, TINY_T.vocab, int(rng.integers(4, 12)))
             .astype(np.int32)) for i in range(4)]
    for srv in (srv_topo, srv_legacy):
        for i, prompt in reqs:
            srv.submit(ServeRequest(i, prompt, 8))
    got = {r.request_id: r.tokens for r in srv_topo.run()}
    ref = {r.request_id: r.tokens for r in srv_legacy.run()}
    assert set(got) == set(ref) == {0, 1, 2, 3}
    for rid in ref:
        assert np.array_equal(got[rid], ref[rid]), rid
    # per-pair summary exists and carries the flat link stats per pair id
    ps = srv_topo.pair_summaries()
    assert set(ps) == {"pair0"}
    assert ps["pair0"]["requests"] == 4
    assert ps["pair0"]["messages"] > 0


# ------------------------------------------------------------ sim factory

def test_build_simulation_pins_pairs_to_links_and_targets():
    spec = two_pair_spec(rtt_fast=2.0, rtt_slow=80.0)
    spec.workload = WorkloadSpec(num_requests=6, max_new=24, rate_per_s=50.0)
    sim = build_simulation(spec)
    # one sim drafter per pair with ITS pair's link
    assert sim.drafter_links is not None and len(sim.drafter_links) == 2
    assert sim.drafter_links[0].spec.rtt_ms == 2.0
    assert sim.drafter_links[1].spec.rtt_ms == 80.0
    an = sim.run()
    assert an.requests, "simulation served nothing"
    for m in an.requests.values():
        # pinned routing: both pairs share the single target node
        assert m.target_id == 0
        assert m.tokens_generated > 0


# ------------------------------------------- process-backed pair spec fields

def test_process_pair_fields_round_trip_and_validate():
    """NodeSpec.address/port and PairSpec.process survive the JSON round
    trip with defaults intact, and a fully-specified process pair
    validates under the restricted regime (greedy + static + distributed
    + continuous)."""
    spec = ClusterSpec(
        nodes=[NodeSpec(id="edge0", role="draft", model="topo-d",
                        address="10.0.0.2", port=7101),
               NodeSpec(id="cloud0", role="target", model="topo-t",
                        address="10.0.0.9", port=7100)],
        pairs=[PairSpec(id="p0", draft="edge0", target="cloud0",
                        window=WindowSpec(kind="static", gamma=4),
                        mode_policy="distributed", process=True)],
        serving=ServingSpec(max_batch=2, temperature=0.0,
                            server="continuous"),
        workload=WorkloadSpec(num_requests=2, max_new=8))
    spec.validate()
    again = ClusterSpec.from_json(spec.to_json())
    assert again == spec
    assert again.nodes[0].address == "10.0.0.2"
    assert again.nodes[1].port == 7100
    assert again.pairs[0].process is True
    # defaults stay default (and keep old topology JSONs loadable)
    legacy = two_pair_spec()
    rt = ClusterSpec.from_dict(legacy.to_dict())
    assert rt.nodes[0].address == "" and rt.nodes[0].port == 0
    assert rt.pairs[0].process is False


def test_build_deployment_rejects_explicit_key_with_process_pairs():
    """Worker hosts rebuild params from spec.seed; an explicit PRNG key
    cannot cross the process boundary and must be rejected up front."""
    spec = ClusterSpec(
        nodes=[NodeSpec(id="edge0", role="draft", model="topo-d"),
               NodeSpec(id="cloud0", role="target", model="topo-t")],
        pairs=[PairSpec(id="p0", draft="edge0", target="cloud0",
                        window=WindowSpec(kind="static", gamma=3),
                        mode_policy="distributed", process=True)],
        serving=ServingSpec(max_batch=1, temperature=0.0,
                            server="continuous"),
        workload=WorkloadSpec(num_requests=1, max_new=4))
    with pytest.raises(TopologyError, match="seed"):
        build_deployment(spec, model_configs=TINY,
                         key=jax.random.PRNGKey(0))


# ------------------------------------------------- published widths / chips

def test_full_width_round_trips_and_resolves_published_widths():
    """ClusterSpec.full_width survives JSON (older files without it load
    as reduced) and switches every registered node to its published
    widths, with the vocabulary unified across nodes."""
    from repro.topology import resolve_node_configs
    spec = one_pair_spec(target="qwen2.5-3b", draft="mamba2-130m")
    assert spec.full_width is False
    spec.full_width = True
    again = ClusterSpec.from_json(spec.to_json())
    assert again == spec and again.full_width is True
    d = spec.to_dict()
    del d["full_width"]
    assert ClusterSpec.from_dict(d).full_width is False

    full, vocab = resolve_node_configs(spec)
    t, dr = full["cloud0"], full["edge0"]
    assert (t.n_layers, t.d_model, t.n_heads, t.n_kv_heads, t.head_dim,
            t.d_ff, t.dtype) == (36, 2048, 16, 2, 128, 11008, "bfloat16")
    assert (dr.n_layers, dr.d_model, dr.ssm_state) == (24, 768, 128)
    assert vocab == t.vocab == dr.vocab == 50280
    small, _ = resolve_node_configs(dataclasses.replace(spec,
                                                        full_width=False))
    assert small["cloud0"].n_layers == 2 and small["cloud0"].vocab == 512


def test_worker_host_resolves_like_build_deployment(tmp_path):
    """A worker host rebuilds exactly the configs and parameters
    build_deployment holds for the same node (one resolution rule, one
    PRNG scheme), so a process-backed pair decodes the in-process pair's
    model."""
    import argparse

    from repro.distributed.host import _HostContext
    spec = ClusterSpec(
        nodes=[NodeSpec("e0", "draft", "mamba2-130m"),
               NodeSpec("e1", "draft", "qwen2.5-3b"),
               NodeSpec("c0", "target", "qwen2.5-3b")],
        pairs=[PairSpec("p0", "e1", "c0", window=WindowSpec("static", 2),
                        mode_policy="distributed")],
        serving=ServingSpec(max_batch=1, gamma_max=2),
        workload=WorkloadSpec(num_requests=1, max_new=4), seed=5)
    dep = build_deployment(spec)
    eng = dep.pairs[0].engine

    path = tmp_path / "topo.json"
    proc = dataclasses.replace(spec, pairs=[dataclasses.replace(
        spec.pairs[0], process=True)])
    path.write_text(proc.to_json())
    ctx = _HostContext(argparse.Namespace(
        topology=str(path), pair="p0", role="target", model_config=[],
        node_params=[]))
    host_eng = ctx.build_engine()
    assert host_eng.draft_cfg == eng.draft_cfg == dep.node_configs["e1"]
    assert host_eng.target_cfg == eng.target_cfg == dep.node_configs["c0"]
    for got, want in ((host_eng.draft_params, eng.draft_params),
                      (host_eng.target_params, eng.target_params)):
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_process_only_deployment_builds_no_local_params(monkeypatch):
    """Nodes that only process-backed pairs use are rebuilt inside their
    worker hosts, so build_deployment draws no parameters for them — the
    parent stays off the chip its workers need."""
    import repro.distributed.host as host
    import repro.topology as topology

    def no_params(*a, **k):
        raise AssertionError("drew parameters for a process-only node")

    monkeypatch.setattr(topology, "node_key", no_params)
    spawned = []
    monkeypatch.setattr(host, "spawn_pair",
                        lambda spec, pair, **kw: spawned.append(
                            (pair.id, kw["first_chip"])) or object())
    spec = ClusterSpec(
        nodes=[NodeSpec("e0", "draft", "topo-d"),
               NodeSpec("e1", "draft", "topo-d"),
               NodeSpec("c0", "target", "topo-t"),
               NodeSpec("c1", "target", "topo-t")],
        pairs=[PairSpec(pid, e, c, window=WindowSpec("static", 2),
                        mode_policy="distributed", process=True)
               for pid, e, c in (("p0", "e0", "c0"), ("p1", "e1", "c1"))],
        serving=ServingSpec(max_batch=1, gamma_max=2),
        workload=WorkloadSpec(num_requests=1, max_new=4))
    dep = build_deployment(spec, model_configs=TINY)
    assert spawned == [("p0", 0), ("p1", 2)]
    assert all(p.engine is None for p in dep.pairs)


def test_compile_cache_dir_comes_from_the_environment_or_the_repo(
        monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins when set, and nothing overrides it;
    otherwise the entry points use the fixed <repo>/.jax_cache."""
    from pathlib import Path

    from repro.launch import compile_cache as cc
    repo = Path(__file__).resolve().parents[1]
    assert cc.cache_dir({}) == str(repo / ".jax_cache")
    assert cc.cache_dir({cc.ENV_VAR: "/data/cache"}) == "/data/cache"

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(cc.ENV_VAR, "/data/cache")
        assert cc.enable_compile_cache() == "/data/cache"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv(cc.ENV_VAR)
        assert cc.enable_compile_cache() == str(repo / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(
            repo / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
