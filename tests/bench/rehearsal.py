"""A cell of the benchmark at reduced width, for CPU rehearsals.

The same harness path as ``bench/run.py`` (weights from the seed, the
program's deployment and server, the probe, the window, the check), with
every model cut to a few narrow layers and the traffic scaled down so a
window takes seconds on a CPU.
"""

from __future__ import annotations

import dataclasses
import json

from bench import spec

TINY = dict(layers=2, d_model=64, heads=4, kv_heads=2, head_dim=16, d_ff=128,
            vocab=512)
TINY_DRAFT = dict(layers=2, d_model=32, heads=2, kv_heads=1, head_dim=16,
                  d_ff=64, vocab=512)
TINY_SAMPLE = 32      # served tokens the reference reads at this size
# mean_logit_gap's limit at this size, between the program's readings on
# the CPU (at most 1.0e-4) and the fp8 control's (at least 1.04e-3)
TINY_LIMIT = 4e-4


def tiny_cell(name: str, slots: int = 4, limit: float = TINY_LIMIT,
              rate: float = 6.0, self_draft: bool = False,
              root=spec.ROOT) -> spec.Cell:
    """Cell ``name`` of ``root``'s ``BENCHMARK.json`` at reduced width
    (each model's family's ``shrink``); with ``self_draft`` its target
    drafts for itself from one parameter tree (accepted windows and bonus
    tokens, which random weights between two models never give)."""
    cell = spec.find_cell(name, root)
    target = cell.target.shrink(**TINY)
    draft = target if self_draft else cell.draft.shrink(**TINY_DRAFT)
    config = json.loads(json.dumps(cell.config))
    config["serving"]["slots"] = slots
    config["correct"].update(mean_logit_gap=limit, sample_tokens=TINY_SAMPLE)
    mix = json.loads(json.dumps(cell.traffic))
    mix["prompt"] = {"median": 20, "sigma": 0.4, "min": 4, "max": 40}
    mix["output"] = {"median": 6, "sigma": 0.5, "min": 2, "max": 12}
    mix["round"] = 4
    if mix["loop"] == "open":
        mix["rate_per_s"] = rate
    else:
        mix["requests"] = 24
    if mix.get("link"):
        mix["link"] = dict(mix["link"], rtt_ms=2.0, jitter_ms=0.2)
    return dataclasses.replace(cell, config=config, traffic=mix,
                               target=target, draft=draft,
                               self_draft=self_draft)
