"""Every traffic mix offers the same work under every seed."""

import json
from pathlib import Path

import numpy as np
import pytest

from bench import traffic

MIXES = sorted(p.stem for p in
               (Path(__file__).resolve().parents[2] / "bench" / "traffic")
               .glob("*.json"))
SEEDS = (7, 3_000_000_019)          # the second needs more than 32 bits
SECONDS = 51.0
VOCAB = 151_936


def load(name):
    return json.loads((Path(traffic.__file__).parent / "traffic"
                       / f"{name}.json").read_text())


@pytest.fixture(scope="module", params=MIXES)
def streams(request):
    mix = load(request.param)
    return mix, [traffic.generate(mix, s, SECONDS, VOCAB) for s in SEEDS]


def test_there_are_mixes():
    assert {"code", "batch", "batch-link20"} <= set(MIXES)


def test_same_length_multisets(streams):
    _, (a, b) = streams
    pairs = [sorted((len(r.prompt), r.max_new_tokens) for r in s)
             for s in (a, b)]
    assert pairs[0] == pairs[1]


def test_same_total_tokens(streams):
    _, (a, b) = streams
    assert sum(len(r.prompt) for r in a) == sum(len(r.prompt) for r in b)
    assert sum(r.max_new_tokens for r in a) == \
        sum(r.max_new_tokens for r in b)


def test_same_arrival_span_and_gaps(streams):
    """One arrival schedule for every seed: the same gaps, span and due
    times; the seed decides which request is due when."""
    mix, (a, b) = streams
    assert [r.arrival_s for r in a] == [r.arrival_s for r in b]
    gaps = np.diff([0.0] + [r.arrival_s for r in a])
    prompts, outputs, want = traffic.shapes(mix, SECONDS)
    np.testing.assert_allclose(np.sort(gaps), np.sort(want), atol=1e-9)
    if mix["loop"] == "open":
        assert a[-1].arrival_s <= SECONDS
        assert a[-1].arrival_s > 0.8 * SECONDS


def test_order_and_ids_differ(streams):
    _, (a, b) = streams
    assert [r.max_new_tokens for r in a] != [r.max_new_tokens for r in b]
    assert not np.array_equal(a[0].prompt[:16], b[0].prompt[:16])


def test_same_seed_same_stream(streams):
    mix, (a, _) = streams
    again = traffic.generate(mix, SEEDS[0], SECONDS, VOCAB)
    assert [r.max_new_tokens for r in again] == [r.max_new_tokens for r in a]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(again, a))


def test_every_round_holds_every_stratum(streams):
    """Any prefix of whole rounds offers the same output work."""
    mix, (a, b) = streams
    k = mix["round"]
    for s in (a, b):
        assert len(s) % k == 0
    ra = [sum(r.max_new_tokens for r in a[:k * j]) for j in (1, 2, 3)]
    rb = [sum(r.max_new_tokens for r in b[:k * j]) for j in (1, 2, 3)]
    for x, y in zip(ra, rb):
        assert abs(x - y) <= 0.25 * max(x, y)


def test_lengths_follow_the_mix(streams):
    mix, (a, _) = streams
    p = np.array([len(r.prompt) for r in a])
    o = np.array([r.max_new_tokens for r in a])
    assert p.min() >= mix["prompt"]["min"] and p.max() <= mix["prompt"]["max"]
    assert o.min() >= mix["output"]["min"] and o.max() <= mix["output"]["max"]
    assert abs(np.median(p) - mix["prompt"]["median"]) <= \
        0.05 * mix["prompt"]["median"]
    assert abs(np.median(o) - mix["output"]["median"]) <= \
        max(1.0, 0.05 * mix["output"]["median"])
    assert all(0 <= r.prompt.min() and r.prompt.max() < VOCAB for r in a)
