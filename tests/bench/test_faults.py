"""A run with its timed path broken underneath comes out not correct.

At reduced width on the CPU, with the chip look left out, the program's
colocated decode step is wrapped so that it breaks one way, and the rest
of the run (warm-up, window, check) goes as on the chip. Each fault a
cell can have: the step returns its state unchanged; half of the batch
(every other slot) is left out, its rows filled from a neighbour's; a
token is altered where it is produced. One chip holds each cell whole,
so no exchange between chips exists to leave out; the emulated link of
``batch-link20`` carries draft windows whose loss greedy verification
absorbs by design, so it is not a fault of the output.
"""

import jax
import jax.numpy as jnp
import pytest

from bench import harness, run
from repro.core.engine import SpecDecodeEngine
from rehearsal import tiny_cell

PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def unchanged(step, args):
    (_, _, state, _, _, _, out_buf, cursor, nacc, nn, _, done, _) = args
    return state, out_buf, cursor, nacc, nn, done


def half_batch(step, args):
    out = step(*args)
    state, out_buf, cursor, nacc, nn, done = out
    b = out_buf.shape[0]
    src = jnp.arange(b) | 1                 # even rows take the odd ones'
    src = jnp.minimum(src, b - 1)
    return state, out_buf[src], cursor, nacc, nn, done


def altered(step, args):
    out = step(*args)
    state, out_buf, cursor, nacc, nn, done = out
    vocab = args[1]["embed"].shape[0]
    return (state, jnp.where(out_buf > 0, (out_buf + 1) % vocab, out_buf),
            cursor, nacc, nn, done)


@pytest.mark.parametrize("workload", ["qwen3b-qwen05b.code",
                                      "qwen3b-qwen05b.batch"])
@pytest.mark.parametrize("fault", [unchanged, half_batch, altered])
def test_a_broken_step_is_not_correct(workload, fault, monkeypatch):
    fused = SpecDecodeEngine._fused_step

    def broken_step(self, gamma_max):
        step = fused(self, gamma_max)

        def run_broken(*args):
            return fault(step, args)
        return run_broken

    monkeypatch.setattr(SpecDecodeEngine, "_fused_step", broken_step)
    monkeypatch.setattr(harness, "DRAIN_LIMIT_S", 3.0)
    monkeypatch.setattr(harness, "WARMUP_LIMIT_S", 20.0)
    cell = tiny_cell(workload, rate=8.0)
    result = run.run_cell(cell, 11, 2.0, False, jax.devices(), PEAKS)
    assert result["correct"] is False, result["checks"]
