"""The one traffic generator: equal work for every seed.

A mix file (``bench/traffic/<name>.json``) gives lognormal prompt and
output lengths (median, sigma, clamp), the loop (``open`` at a fixed
``rate_per_s``, or a ``backlog`` of ``requests`` all due at once) and a
``round`` size. The shapes follow the seeded lognormal streams of
``repro.fleet.workload.generate_requests``, with one change: nothing is
drawn at random except order and token ids.

- Lengths are lognormal quantiles at (i + 1/2)/N and gaps are
  exponential quantiles at (i + 1/2)/N, so every seed offers the same
  multiset of (prompt, output) pairs and of gaps: the same total prompt
  tokens, output tokens and arrival span.
- Prompt quantile i is paired with output quantile ``PAIRING[i]``, a
  permutation fixed by the mix (not by the seed), so the pairs' multiset
  is fixed too.
- ``--seed`` permutes the order of the requests, by rounds: the N
  requests are cut into ``round`` strata by output length, and each
  round of ``round`` consecutive requests holds one member of every
  stratum, so any prefix of whole rounds offers nearly the same work
  under every seed. The seed also draws every prompt token id.
- The arrival schedule is the same for every seed: the gaps are put in
  an order fixed by the mix (rounds of one gap from each of ``round``
  strata, as a replayed trace would be), and the seed decides which
  request arrives at each due time.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np

PAIRING_SEED = 20231118      # fixes a mix's pairing and arrival schedule


@dataclass(frozen=True)
class Request:
    request_id: int
    prompt: np.ndarray          # (S,) int32
    max_new_tokens: int
    arrival_s: float


def lognormal_quantiles(n: int, median: float, sigma: float, lo: int,
                        hi: int) -> np.ndarray:
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    return np.clip(np.rint(median * np.exp(sigma * z)), lo, hi).astype(
        np.int64)


def exponential_quantiles(n: int, rate: float) -> np.ndarray:
    q = (np.arange(n) + 0.5) / n
    return -np.log1p(-q) / rate


def request_count(mix: dict, seconds: float) -> int:
    """N: a backlog's fixed size, or the whole rounds an open loop offers
    in ``seconds`` at its rate."""
    k = int(mix["round"])
    if mix["loop"] == "backlog":
        return k * math.ceil(int(mix["requests"]) / k)
    return k * max(1, int(mix["rate_per_s"] * seconds) // k)


def _rounds_order(n: int, k: int, sort_key: np.ndarray,
                  rng: np.random.Generator) -> np.ndarray:
    """Indices 0..n-1 in an order whose every round of k holds one member
    of each of the k strata of ``sort_key`` (n is a multiple of k)."""
    r = n // k
    strata = np.argsort(sort_key, kind="stable").reshape(k, r)
    members = np.stack([row[rng.permutation(r)] for row in strata])  # (k, r)
    order = []
    for j in range(r):
        order.extend(members[rng.permutation(k), j])
    return np.asarray(order, np.int64)


def shapes(mix: dict, seconds: float) -> tuple[np.ndarray, np.ndarray,
                                               np.ndarray]:
    """The seed-independent multisets: (prompt lengths, output lengths)
    as fixed pairs, and the gaps (zeros for a backlog)."""
    n = request_count(mix, seconds)
    p, o = mix["prompt"], mix["output"]
    prompts = lognormal_quantiles(n, p["median"], p["sigma"], p["min"],
                                  p["max"])
    outputs = lognormal_quantiles(n, o["median"], o["sigma"], o["min"],
                                  o["max"])
    outputs = outputs[np.random.default_rng(PAIRING_SEED).permutation(n)]
    if mix["loop"] == "backlog":
        gaps = np.zeros(n)
    else:
        gaps = exponential_quantiles(n, float(mix["rate_per_s"]))
    return prompts, outputs, gaps


def generate(mix: dict, seed: int, seconds: float, vocab: int
             ) -> list[Request]:
    """The seeded request stream of one run, in arrival order."""
    prompts, outputs, gaps = shapes(mix, seconds)
    n, k = len(prompts), int(mix["round"])
    rng = np.random.default_rng(seed)
    order = _rounds_order(n, k, outputs, rng)
    gap_order = _rounds_order(n, k, gaps,
                              np.random.default_rng(PAIRING_SEED + 1))
    arrivals = np.cumsum(gaps[gap_order]) if mix["loop"] == "open" \
        else np.zeros(n)
    reqs = []
    for rid, (i, t) in enumerate(zip(order, arrivals)):
        ids = rng.integers(0, vocab, int(prompts[i]), dtype=np.int32)
        reqs.append(Request(rid, ids, int(outputs[i]), float(t)))
    return reqs


def max_lengths(mix: dict, seconds: float) -> tuple[int, int]:
    """(longest prompt, longest output): the shapes a run compiles."""
    prompts, outputs, _ = shapes(mix, seconds)
    return int(prompts.max()), int(outputs.max())
