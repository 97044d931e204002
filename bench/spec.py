"""What one cell is, read from ``BENCHMARK.json`` and the files it names.

Everything that belongs to one configuration, traffic mix or metric is a
file of its own, found by name:

- a configuration: the file its ``configs`` entry names
  (``bench/configs/<name>.json``), whose models are
  ``bench/models/<model>.json`` (published HF ``config.json`` numbers)
  and whose plain reference is ``bench/reference/<reference>.py``;
- a traffic mix: ``bench/traffic/<traffic>.json``, read by the one
  generator in :mod:`bench.traffic`;
- a metric: ``bench/metrics/<name>.py``, or, for a metric split by
  traffic (``tokens_per_pass.code``), ``bench/metrics/<base>.py``.

Adding a cell, configuration, mix or metric is adding files and entries.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class SpecError(ValueError):
    """A cell, configuration or traffic file is missing or malformed."""


@dataclasses.dataclass(frozen=True)
class ModelDims:
    """One model at its published widths (HF ``config.json`` names)."""
    name: str
    source: str
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    norm_eps: float
    tied: bool
    dtype: str

    @classmethod
    def load(cls, name: str) -> "ModelDims":
        path = BENCH / "models" / f"{name}.json"
        if not path.is_file():
            raise SpecError(f"no model file {path}")
        c = json.loads(path.read_text())
        return cls(name=name, source=c["source"],
                   layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                   heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"],
                   head_dim=c.get("head_dim",
                                  c["hidden_size"] // c["num_attention_heads"]),
                   d_ff=c["intermediate_size"], vocab=c["vocab_size"],
                   rope_theta=float(c["rope_theta"]),
                   norm_eps=float(c["rms_norm_eps"]),
                   tied=bool(c["tie_word_embeddings"]),
                   dtype=c["torch_dtype"])

    # -- sizes the work counter and the trace reduction use ---------------

    def layer_params(self) -> int:
        d, hd = self.d_model, self.head_dim
        attn = d * hd * (2 * self.heads + 2 * self.kv_heads) \
            + hd * (self.heads + 2 * self.kv_heads)
        return attn + 3 * d * self.d_ff + 2 * d

    def params(self) -> int:
        emb = self.vocab * self.d_model * (1 if self.tied else 2)
        return emb + self.layers * self.layer_params() + self.d_model

    def matmul_params(self) -> int:
        """Weights one token multiplies by: every layer's projections and
        the output head (the embedding lookup is a gather)."""
        d, hd = self.d_model, self.head_dim
        per = d * hd * (2 * self.heads + 2 * self.kv_heads) \
            + 3 * d * self.d_ff
        return self.layers * per + self.vocab * d

    def kv_bytes_per_position(self, itemsize: int = 2) -> int:
        return self.layers * 2 * self.kv_heads * self.head_dim * itemsize


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: dict
    traffic: dict
    target: ModelDims
    draft: ModelDims          # the target itself for a self-drafting pair
    self_draft: bool

    @property
    def serving(self) -> dict:
        return self.config["serving"]


def load_benchmark(root: Path = ROOT) -> dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise SpecError(f"no {path}")
    return json.loads(path.read_text())


def load_config(entry: dict, root: Path = ROOT) -> dict:
    path = root / entry["file"]
    if not path.is_file():
        raise SpecError(f"configuration {entry['name']}: no file {path}")
    return json.loads(path.read_text())


def load_traffic(name: str) -> dict:
    path = BENCH / "traffic" / f"{name}.json"
    if not path.is_file():
        raise SpecError(f"traffic {name!r}: no file {path}")
    return json.loads(path.read_text())


def config_models(config: dict) -> tuple[ModelDims, ModelDims, bool]:
    """(target, draft, self_draft) of a configuration file: the target's
    numbers sit at the top level, the draft is a model file or ``self``."""
    target = ModelDims.load(config["target_model"])
    model = json.loads((BENCH / "models" /
                        f"{config['target_model']}.json").read_text())
    for key in ("num_hidden_layers", "hidden_size", "intermediate_size",
                "num_attention_heads", "num_key_value_heads", "vocab_size",
                "rms_norm_eps", "rope_theta", "tie_word_embeddings",
                "torch_dtype"):
        if config[key] != model[key]:
            raise SpecError(f"configuration {key}={config[key]!r} differs "
                            f"from the model file's {model[key]!r}")
    if config["draft_model"] == "self":
        return target, target, True
    return target, ModelDims.load(config["draft_model"]), False


def find_cell(workload: str, root: Path = ROOT) -> Cell:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SpecError(f"unknown workload {workload!r}; cells: "
                        f"{sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_config(configs[w["config"]], root)
    target, draft, self_draft = config_models(config)
    return Cell(name=workload, config_name=w["config"],
                traffic_name=w["traffic"], chips=int(w["chips"]),
                config=config, traffic=load_traffic(w["traffic"]),
                target=target, draft=draft, self_draft=self_draft)


def cell_metrics(workload: str, trace: bool, root: Path = ROOT
                 ) -> list[dict]:
    """The metric entries a run of ``workload`` reports: its end-to-end
    metrics without trace, its per-layer metrics with it (an entry with a
    ``workloads`` list applies to those cells alone)."""
    bench = load_benchmark(root)
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group
            if "workloads" not in m or workload in m["workloads"]]


def metric_reader(name: str) -> ModuleType:
    """The reader module of metric ``name``: ``metrics/<name>.py``, else
    ``metrics/<base>.py`` for a name split by traffic (``base.suffix``)."""
    for stem in (name, name.split(".", 1)[0]):
        path = BENCH / "metrics" / f"{stem}.py"
        if path.is_file():
            spec = importlib.util.spec_from_file_location(
                f"bench_metric_{stem.replace('.', '_').replace('-', '_')}",
                path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod
    raise SpecError(f"metric {name!r}: no reader under {BENCH / 'metrics'}")


def reference_module(config: dict) -> ModuleType:
    name = config["reference"]
    path = BENCH / "reference" / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"no plain reference {path}")
    spec = importlib.util.spec_from_file_location(f"bench_reference_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
