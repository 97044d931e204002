"""The work a decode pass needs, counted from shapes by the benchmark.

Per pass with decided window γ (0 for a fused, target-only pass) over
``a`` active slots whose live contexts are c_s:

- target: its matmul weights read once; 2·P_t FLOPs per token over the
  γ+1 window tokens of every active slot; attention 4·L·H·hd·c_s FLOPs
  per window token, and the live KV (c_s positions) read once per slot;
- draft: per decided window token, its matmul weights read once and
  2·P_d FLOPs per active slot, plus attention over its live KV.

The window width the program pads to (γ_max), copies of caches, the
output head's positions beyond the one a token needs, and draft work in
fused passes are program waste and are not counted, so a share computed
from these counts cannot exceed 100% unless the device time leaves work
out.
"""

from __future__ import annotations

import dataclasses

from bench.spec import ModelDims

BYTES = 2       # bf16 weights and KV


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


def attn_flops(m: ModelDims, context: int) -> float:
    """FLOPs of one query token attending over ``context`` positions."""
    return 4.0 * m.layers * m.heads * m.head_dim * context


def pass_work(target: ModelDims, draft: ModelDims, gamma: int,
              contexts: list[int]) -> Work:
    a = len(contexts)
    if a == 0:
        return Work(passes=1)
    w = Work(passes=1)
    t_tokens = gamma + 1
    w.flops += 2.0 * target.matmul_params() * a * t_tokens
    w.flops += sum(attn_flops(target, c) * t_tokens for c in contexts)
    w.bytes += target.matmul_params() * BYTES
    w.bytes += sum(c * target.kv_bytes_per_position(BYTES)
                   for c in contexts)
    if gamma > 0:
        w.flops += gamma * (2.0 * draft.matmul_params() * a
                            + sum(attn_flops(draft, c) for c in contexts))
        w.bytes += gamma * (draft.matmul_params() * BYTES
                            + sum(c * draft.kv_bytes_per_position(BYTES)
                                  for c in contexts))
    return w


def chunks_work(target: ModelDims, draft: ModelDims, chunks) -> Work:
    """Needed work of every pass of the probe's chunks (contexts as at
    the chunk's start, so growth inside a chunk is not counted)."""
    total = Work()
    for ch in chunks:
        for g in ch.gammas[:ch.passes]:
            total.add(pass_work(target, draft, g, ch.contexts))
    return total
