"""The control comes out not correct.

The control is the plain reference in the program's place at the
precision below the configuration's bf16: weight-only fp8
(float8_e4m3fn, one scale per output channel). At reduced width on the
CPU, a run with ``--control fp8`` reads, on the same served prompts and
tokens, the gap of the token the control puts first, and the same
comparison as a benchmark run finds it not correct, where the program's
own run of that seed passes with a limit set between the two readings.
``bench/run.py --control fp8`` takes the same readings on the chip at
each cell's own size.
"""

import jax
import pytest

from bench import run
from rehearsal import TINY_LIMIT, tiny_cell

PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


@pytest.mark.parametrize("workload,self_draft", [
    pytest.param("qwen3b-qwen05b.code", False, id="qwen3b-qwen05b.code"),
    pytest.param("qwen3b-qwen05b.batch", True, id="qwen3b-self.batch"),
])
@pytest.mark.parametrize("seed", [21, 4_000_000_003])
def test_control_fails_where_the_program_passes(workload, self_draft, seed):
    cell = tiny_cell(workload, self_draft=self_draft)
    program = run.run_cell(cell, seed, 2.0, False, jax.devices(), PEAKS)
    control = run.run_cell(cell, seed, 2.0, False, jax.devices(), PEAKS,
                           control="fp8")
    prog, ctrl = (r["checks"]["mean_logit_gap"]["value"]
                  for r in (program, control))
    assert program["correct"], program["checks"]
    assert control["correct"] is False, control["checks"]
    assert prog <= TINY_LIMIT < ctrl and ctrl >= 3 * prog
    n = control["checks"]["tokens_compared"]
    assert n["value"] >= n["limit"]
