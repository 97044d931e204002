"""Program spans (``repro.tracing``) in the profiler's trace of a serve.

A toy pair serves a few requests under ``jax.profiler.trace`` on the
colocated path and over an emulated link (fused, distributed and pipelined
rounds). The host plane must hold each layer's ``dsd.*`` span, nested as
the module docstring of ``repro.tracing`` says, with the admission's
metadata; tracing changes neither the greedy tokens nor the compiled
programs.
"""

from __future__ import annotations

import glob
import re
from pathlib import Path

import jax
import numpy as np
import pytest

from conformance.scenarios import GAMMA, make_engine, make_noised_engine
from repro.analysis.sanitize import compile_guard
from repro.core.window import StaticWindowPolicy
from repro.distributed import EmulatedLinkTransport
from repro.serving import ServeRequest, ServerConfig, SpecDecodeServer
from repro.sim.network import LinkSpec
from repro.tracing import PREFIX, SPANS

SRC = Path(__file__).resolve().parents[1] / "src" / "repro"

COMMON = {"dsd.serve", "dsd.serve.idle", "dsd.serve.route", "dsd.admit",
          "dsd.admit.dispatch", "dsd.admit.wait", "dsd.chunk", "dsd.round",
          "dsd.decide", "dsd.chunk.sync", "dsd.chunk.commit", "dsd.retire"}
TRANSPORT = {"dsd.propose", "dsd.verify", "dsd.verdict.sync",
             "dsd.link.window", "dsd.link.verdict"}
# path -> (draft family, mode policy, spans the serve must write)
PATHS = {
    "colocated": ("dense", None, COMMON | {"dsd.step.dispatch"}),
    "fused": ("dense", "fused", COMMON | {"dsd.verify", "dsd.ingest",
                                          "dsd.verdict.sync",
                                          "dsd.link.control"}),
    "distributed": ("dense", "distributed", COMMON | TRANSPORT),
    "pipeline": ("dense", "pipeline", COMMON | TRANSPORT),
    # a recurrent draft re-advances its state after each verdict
    "recurrent": ("ssm", "distributed", COMMON | TRANSPORT
                  | {"dsd.advance"}),
}


def _requests():
    rng = np.random.default_rng(11)
    # the first arrival comes after the loop starts: the loop idles first
    return [ServeRequest(i, rng.integers(0, 128, 5 + 2 * i).astype(np.int32),
                         6 + i, arrival_s=0.02 + 0.01 * i)
            for i in range(3)]


def _serve(eng, mode):
    transport = None if mode is None else EmulatedLinkTransport(
        LinkSpec(rtt_ms=4.0, jitter_ms=0.0), seed=0, sleep=False)
    cfg = ServerConfig(max_batch=2, sync_every=2, max_prompt_len=16,
                       max_new_cap=8, transport=transport,
                       mode_policy=mode or "auto")
    server = SpecDecodeServer(eng, StaticWindowPolicy(GAMMA), cfg)
    for r in _requests():
        server.submit(r)
    return {r.request_id: r.tokens for r in server.run()}


def _host_spans(trace_dir) -> list[tuple[str, float, float, dict]]:
    path = sorted(glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb"))[-1]
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    out.append((e.name, float(e.start_ns),
                                float(e.start_ns + e.duration_ns),
                                dict(e.stats)))
    return out


def _inside(inner, outers) -> bool:
    return any(o[1] <= inner[1] and inner[2] <= o[2] for o in outers)


@pytest.fixture(scope="module")
def engines():
    return {"dense": make_engine("dense"), "ssm": make_noised_engine("ssm")}


@pytest.mark.filterwarnings("ignore:builtin type event_stats")
@pytest.mark.parametrize("path", sorted(PATHS))
def test_serve_writes_every_layer_span(engines, path, tmp_path):
    family, mode, expected = PATHS[path]
    engine = engines[family]
    untraced = _serve(engine, mode)       # also compiles every program
    with compile_guard(allowed=0, what=f"traced {path} serve"):
        with jax.profiler.trace(str(tmp_path)):
            traced = _serve(engine, mode)
    assert traced.keys() == untraced.keys()
    for rid, toks in untraced.items():
        np.testing.assert_array_equal(traced[rid], toks)

    spans = _host_spans(tmp_path)
    names = {s[0] for s in spans}
    assert expected <= names, sorted(expected - names)
    assert names <= set(SPANS)
    by = {n: [s for s in spans if s[0] == n] for n in names}
    assert len(by["dsd.serve"]) == 1
    assert all(_inside(c, by["dsd.serve"]) for c in by["dsd.chunk"])
    assert all(_inside(r, by["dsd.chunk"]) for r in by["dsd.round"])
    assert all(_inside(a, by["dsd.admit"]) for a in by["dsd.admit.wait"])
    for name in ("dsd.serve.idle", "dsd.serve.route", "dsd.admit",
                 "dsd.retire"):
        assert all(_inside(s, by["dsd.serve"]) for s in by[name]), name
    # admissions follow the route that chose them, outside it and
    # outside every chunk
    for outer in ("dsd.serve.route", "dsd.chunk"):
        assert not any(_inside(a, by[outer]) for a in by["dsd.admit"])
    if "dsd.step.dispatch" in expected:
        assert all(_inside(d, by["dsd.round"])
                   for d in by["dsd.step.dispatch"])
    for name in expected - COMMON:
        # every round-level span sits in a chunk
        assert all(_inside(s, by["dsd.chunk"]) for s in by[name]), name
    if "dsd.link.control" in expected:
        # token-stream flushes happen inside fused rounds (and at most
        # once a chunk after them, when the batch drains)
        assert any(_inside(c, by["dsd.round"])
                   for c in by["dsd.link.control"])

    admits = sorted(by["dsd.admit"], key=lambda s: s[3]["request_id"])
    reqs = _requests()
    assert [a[3]["request_id"] for a in admits] == [r.request_id
                                                   for r in reqs]
    for a, r in zip(admits, reqs):
        assert a[3]["arrival_s"] == pytest.approx(r.arrival_s)
        assert a[3]["prompt_len"] == len(r.prompt)
    commits = by["dsd.chunk.commit"]
    assert sum(c[3]["tokens"] for c in commits) == sum(
        len(t) - 1 for t in untraced.values())
    rounds = by["dsd.round"]
    fused = [r[3]["fused"] for r in rounds]
    assert all(fused) if path == "fused" else not any(fused)


def test_span_names_are_the_tuple():
    """Every name a call site passes is in ``SPANS``, and every entry of
    ``SPANS`` has a call site."""
    used = set()
    for f in SRC.rglob("*.py"):
        used |= set(re.findall(r'\bspan\(\s*"([a-z.]+)"', f.read_text()))
    assert {PREFIX + u for u in used} == set(SPANS)
