"""Each cell's run on the CPU at reduced width: the harness path of
``bench/run.py`` (weights from the seed, the program's deployment and
``SpecDecodeServer.run``, the probe, the window, the metrics, the
reference check) with the chip look left out."""

import json

import jax
import pytest

from bench import run, spec
from rehearsal import TINY_SAMPLE, tiny_cell

# (cell, self-drafting): the self-drafting pair accepts draft windows
WORKLOADS = [
    pytest.param("qwen3b-qwen05b.code", False, id="qwen3b-qwen05b.code"),
    pytest.param("qwen3b-qwen05b.batch", True, id="qwen3b-self.batch"),
    pytest.param("qwen3b-qwen05b.batch", False, id="qwen3b-qwen05b.batch"),
    pytest.param("qwen3b-qwen05b.batch-link20", False,
                 id="qwen3b-qwen05b.batch-link20"),
]
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


@pytest.mark.parametrize("workload,self_draft", WORKLOADS)
def test_cell_runs_correct_at_reduced_width(workload, self_draft):
    cell = tiny_cell(workload, self_draft=self_draft)
    result = run.run_cell(cell, 3_000_000_007, 2.0, False, jax.devices(),
                          PEAKS)
    json.dumps(result)                      # the result line is JSON
    assert result["correct"], result["checks"]
    assert list(result)[-1] == "checks"
    assert result["failed"] == 0 and result["attempted"] > 0
    names = {m["name"] for m in spec.cell_metrics(workload, False)}
    assert set(result["metrics"]) == names
    assert result["metrics"]["setup_s"]["value"] > 0
    assert result["checks"]["tokens_compared"]["value"] >= TINY_SAMPLE
