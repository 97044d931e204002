"""An architecture is a family file found by name.

The qwen2 family gives the very weights, program configuration and
needed work the benchmark had before architectures were split out (the
digests and fields below were read on the tree before the split), and a
second family, with its model file, reference, configuration and cell,
comes in by new files and entries alone.
"""

import dataclasses
import hashlib
import json
import shutil
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import run, spec, weights, work
from rehearsal import TINY, TINY_DRAFT, TINY_SAMPLE, tiny_cell

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
WEIGHTS_SEED = 1305
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}

# sha256 over every leaf (path, dtype, shape, bytes) at TINY / TINY_DRAFT
# widths, weights seed 1305, read before the split
PARENT_DIGESTS = {
    ("program", 0):
        "b47baac0303c6b2e15de2a5624e9898a1ea1f2fd00682811113a797e8f3ba384",
    ("reference", 0):
        "27f37c6af228e06558e8ea5d563751e2ffdb038de78f92967605a2fca5566fb4",
    ("program", 1):
        "85ef35ef3f66d93fdef2763f35a2456fe939c21e04642be60bc1fee679a00d2a",
    ("reference", 1):
        "b856aac941fa58d32e1859809f1d435ec6f6d85455372144772ccc81c7e9a162",
}
# the ModelConfig the harness built before the split, at published widths
PARENT_CONFIGS = {
    "qwen2.5-3b": dict(
        name="bench-qwen2.5-3b", arch_type="dense", n_layers=36,
        d_model=2048, n_heads=16, n_kv_heads=2, d_ff=11008, vocab=151936,
        head_dim=128, qkv_bias=True, rope_theta=1000000.0, norm_eps=1e-06,
        tie_embeddings=True, dtype="bfloat16"),
    "qwen2.5-0.5b": dict(
        name="bench-qwen2.5-0.5b", arch_type="dense", n_layers=24,
        d_model=896, n_heads=14, n_kv_heads=2, d_ff=4864, vocab=151936,
        head_dim=64, qkv_bias=True, rope_theta=1000000.0, norm_eps=1e-06,
        tie_embeddings=True, dtype="bfloat16"),
}


def digest(tree) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)}|{a.dtype}|{a.shape}|"
                 .encode())
        h.update(a.tobytes())
    return h.hexdigest()


def reference_leaves(m, slot: int) -> dict:
    key = weights.model_key(WEIGHTS_SEED, slot)
    return {"layers": [weights.layer_leaves(m, key, layer)
                       for layer in range(m.layers)],
            "global": weights.global_leaves(m, key)}


@pytest.mark.parametrize("side,slot", sorted(PARENT_DIGESTS))
def test_qwen2_leaves_are_the_parents(side, slot):
    model, widths = (("qwen2.5-3b", TINY), ("qwen2.5-0.5b", TINY_DRAFT))[slot]
    m = spec.load_model(model).shrink(**widths)
    tree = weights.program_params(m, WEIGHTS_SEED, slot) \
        if side == "program" else reference_leaves(m, slot)
    assert digest(tree) == PARENT_DIGESTS[side, slot]


@pytest.mark.parametrize("model", sorted(PARENT_CONFIGS))
def test_qwen2_program_config_is_the_parents(model):
    from repro.configs.base import ModelConfig
    m = spec.load_model(model)
    assert dataclasses.asdict(spec.family_of(m).program_config(m)) == \
        dataclasses.asdict(ModelConfig(**PARENT_CONFIGS[model]))


def add_toy_family(root: Path) -> None:
    """Copies the benchmark to ``root`` and adds, as files and entries
    only, the family ``qwen2nobias`` (no q/k/v bias, untied head), its
    model, its reference, a configuration that pairs it with the qwen2
    draft, and a cell."""
    bench = root / "bench"
    shutil.copytree(ROOT / "bench", bench)
    shutil.copy(DATA / "family_qwen2nobias.py",
                bench / "families/qwen2nobias.py")
    model = json.loads((ROOT / "bench/models/qwen2.5-3b.json").read_text())
    model.update(family="qwen2nobias", tie_word_embeddings=False,
                 head_dim=128, attention_bias="none")
    (bench / "models/toy-nobias.json").write_text(json.dumps(model))
    ref = (ROOT / "bench/reference/qwen2.py").read_text()
    for b in ("bq", "bk", "bv"):       # the reference without the biases
        line = f' + w["{b}"]\n'
        assert ref.count(line) == 1
        ref = ref.replace(line, "\n")
    (bench / "reference/qwen2nobias.py").write_text(ref)
    config = json.loads(
        (ROOT / "bench/configs/qwen3b-qwen05b.json").read_text())
    config.update(target_model="toy-nobias", reference="qwen2nobias",
                  tie_word_embeddings=False)
    (bench / "configs/toy-nobias.json").write_text(json.dumps(config))
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    bm["configs"].append({"name": "toy-nobias", "source": model["source"],
                          "file": "bench/configs/toy-nobias.json",
                          "reduced": [], "why": "a toy family"})
    bm["workloads"].append({"name": "toy-nobias.code", "config": "toy-nobias",
                            "traffic": "code", "chips": 1,
                            "why": "a toy family under the code mix"})
    (root / "BENCHMARK.json").write_text(json.dumps(bm))


@pytest.fixture
def toy_root(tmp_path, monkeypatch):
    add_toy_family(tmp_path)
    monkeypatch.setattr(spec, "BENCH", tmp_path / "bench")
    return tmp_path


def test_a_new_family_is_found_by_name(toy_root):
    cell = spec.find_cell("toy-nobias.code", toy_root)
    t, d = cell.target, cell.draft
    assert (t.family, d.family) == ("qwen2nobias", "qwen2")
    assert spec.family_of(t).__file__ == str(
        toy_root / "bench/families/qwen2nobias.py")
    qwen = spec.load_model("qwen2.5-3b")
    biases = 36 * 128 * (16 + 2 * 2)
    assert t.params() == qwen.params() - biases + 151936 * 2048
    cfg = spec.family_of(t).program_config(t)
    assert (cfg.qkv_bias, cfg.tie_embeddings) == (False, False)
    w = work.pass_work(t, d, 3, [1000, 2000])
    assert w.flops == t.pass_flops(4, [1000, 2000]) \
        + 3 * d.pass_flops(1, [1000, 2000])
    assert w.bytes == t.pass_bytes(4, [1000, 2000]) \
        + 3 * d.pass_bytes(1, [1000, 2000])
    tiny = t.shrink(**TINY)
    tree = weights.program_params(tiny, WEIGHTS_SEED, 0)
    assert set(tree["layers"]["attn"]) == {"wq", "wk", "wv", "wo"}
    g = weights.global_leaves(tiny, weights.model_key(WEIGHTS_SEED, 0))
    assert list(g) == ["embed", "final_norm", "lm_head"]
    np.testing.assert_array_equal(tree["lm_head"], g["lm_head"].T)
    # a tied model keeps its keys: the head is drawn after the final norm
    q = reference_leaves(qwen.shrink(**TINY), 0)["global"]
    np.testing.assert_array_equal(g["embed"], q["embed"])
    np.testing.assert_array_equal(g["final_norm"], q["final_norm"])


def test_a_new_family_rehearsal_is_correct(toy_root):
    cell = tiny_cell("toy-nobias.code", root=toy_root)
    result = run.run_cell(cell, 3_000_000_019, 2.0, False, jax.devices(),
                          PEAKS)
    assert result["correct"], result["checks"]
    assert result["checks"]["tokens_compared"]["value"] >= TINY_SAMPLE
    assert result["metrics"]["setup_s"]["value"] > 0


def test_an_unknown_family_is_a_spec_error(tmp_path, monkeypatch):
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    path = tmp_path / "bench/models/qwen2.5-3b.json"
    model = json.loads(path.read_text())
    path.write_text(json.dumps(dict(model, family="no-such-family")))
    monkeypatch.setattr(spec, "BENCH", tmp_path / "bench")
    with pytest.raises(spec.SpecError):
        spec.load_model("qwen2.5-3b")
    del model["family"]
    path.write_text(json.dumps(model))
    with pytest.raises(spec.SpecError):
        spec.load_model("qwen2.5-3b")


def test_a_key_the_config_shares_with_its_model_has_to_agree():
    config = json.loads(
        (ROOT / "bench/configs/qwen3b-qwen05b.json").read_text())
    spec.config_models(config)
    with pytest.raises(spec.SpecError):
        spec.config_models(dict(config, use_sliding_window=True))
