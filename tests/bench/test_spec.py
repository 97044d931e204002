"""BENCHMARK.json keeps to its contract, and every cell, configuration,
traffic mix and metric is found by name, so a later change adds one by
adding files and entries."""

import json
import re
import shutil
from pathlib import Path

import pytest

from bench import run, spec

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
WORKLOADS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"][:2] == ["python3", "bench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    for p in BENCH["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/") and ".." not in p


def test_names_units_and_bounds():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        for w in m.get("workloads", []):
            assert w in WORKLOADS
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        # a per-layer metric lists cells that report what it moves
        for w in m["workloads"]:
            moved = [e for e in BENCH["end_to_end"] if e["name"] == m["moves"]]
            assert "workloads" not in moved[0] or w in moved[0]["workloads"]
    assert all(0 < len(m["layer"]) <= 200 and "\n" not in m["layer"]
               for m in BENCH["per_layer"])


@pytest.mark.parametrize("workload", WORKLOADS)
def test_every_cell_resolves_by_name(workload):
    cell = spec.find_cell(workload, ROOT)
    assert cell.chips == 1
    assert cell.serving["slots"] >= 1
    e2e = [m["name"] for m in spec.cell_metrics(workload, False, ROOT)]
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = spec.cell_metrics(workload, True, ROOT)
    assert layer
    for m in spec.cell_metrics(workload, False, ROOT) + layer:
        assert callable(spec.metric_reader(m["name"]).read)
    assert callable(spec.reference_module(cell.config).logits_at)


def test_configs_are_under_paths_and_used():
    used = {w["config"] for w in BENCH["workloads"]}
    files = set()
    for c in BENCH["configs"]:
        assert c["name"] in used
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
        assert c["file"] not in files
        files.add(c["file"])
        cfg = spec.load_config(c, ROOT)
        target, draft, _ = spec.config_models(cfg)
        assert target.d_model == cfg["hidden_size"]
        assert c["reduced"] == []


def test_a_new_mix_and_metric_are_found_by_name(tmp_path, monkeypatch):
    """Adding a traffic file, a metric reader and their entries is all a
    new cell or metric takes."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    mix = json.loads((ROOT / "bench/traffic/code.json").read_text())
    mix["rate_per_s"] = 0.5
    (tmp_path / "bench/traffic/code-slow.json").write_text(json.dumps(mix))
    (tmp_path / "bench/metrics/admit_count.py").write_text(
        "def read(ctx):\n    return len(ctx.window.probe.admits)\n")
    bench["workloads"].append({"name": "qwen3b-qwen05b.code-slow",
                               "config": "qwen3b-qwen05b",
                               "traffic": "code-slow", "chips": 1,
                               "why": "a slower open loop"})
    bench["per_layer"].append({"name": "admit_count", "unit": "requests",
                               "better": "higher",
                               "source": "program_counter",
                               "layer": "scheduler",
                               "moves": "ttft_p95_ms",
                               "workloads": ["qwen3b-qwen05b.code-slow"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setattr(spec, "BENCH", tmp_path / "bench")
    cell = spec.find_cell("qwen3b-qwen05b.code-slow", tmp_path)
    assert cell.traffic["rate_per_s"] == 0.5
    names = [m["name"] for m in
             spec.cell_metrics("qwen3b-qwen05b.code-slow", True, tmp_path)]
    assert names == ["admit_count"]
    assert spec.metric_reader("admit_count").__file__.startswith(
        str(tmp_path))


def test_split_metric_names_share_a_reader():
    assert spec.metric_reader("tokens_per_pass.code").__file__.endswith(
        "tokens_per_pass.py")
    with pytest.raises(spec.SpecError):
        spec.metric_reader("no_such_metric")


def test_peaks_are_keyed_by_device_kind():
    p = run.peaks_of("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in p["source"]
    with pytest.raises(SystemExit):
        run.peaks_of("TPU v99")


def test_no_accelerator_is_an_error():
    import jax
    if jax.devices()[0].platform != "cpu":
        pytest.skip("an accelerator is present")
    with pytest.raises(SystemExit):
        run.accelerator(1)
