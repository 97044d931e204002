"""The work a decode pass needs, counted from shapes by the benchmark.

A pass with decided window γ (0 for a fused, target-only pass) over
active slots whose live contexts are c_s needs one target forward of
γ+1 window tokens on every active slot and, for γ > 0, γ draft forwards
of one token each. Each model's family counts the needed FLOPs and bytes
of one forward (``Dims.pass_flops`` / ``pass_bytes``; the rules are in
the family's file). The window width the program pads to (γ_max) and
draft work in fused passes are program waste and are not counted, so a
share computed from these counts cannot exceed 100% unless the device
time leaves work out.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass
class Work:
    flops: float = 0.0
    bytes: float = 0.0
    passes: int = 0

    def __add__(self, other: "Work") -> "Work":
        return Work(self.flops + other.flops, self.bytes + other.bytes,
                    self.passes + other.passes)

    def add(self, other: "Work") -> None:
        self.flops += other.flops
        self.bytes += other.bytes
        self.passes += other.passes


def pass_work(target, draft, gamma: int, contexts: list[int]) -> Work:
    w = Work(passes=1)
    if not contexts:
        return w
    w.flops += target.pass_flops(gamma + 1, contexts)
    w.bytes += target.pass_bytes(gamma + 1, contexts)
    if gamma > 0:
        w.flops += gamma * draft.pass_flops(1, contexts)
        w.bytes += gamma * draft.pass_bytes(1, contexts)
    return w


def chunks_work(target, draft, chunks) -> Work:
    """Needed work of every pass of the probe's chunks (contexts as at
    the chunk's start, so growth inside a chunk is not counted)."""
    total = Work()
    for ch in chunks:
        for g in ch.gammas[:ch.passes]:
            total.add(pass_work(target, draft, g, ch.contexts))
    return total
