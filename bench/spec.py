"""What one cell is, read from ``BENCHMARK.json`` and the files it names.

Everything that belongs to one configuration, traffic mix or metric is a
file of its own, found by name:

- a configuration: the file its ``configs`` entry names
  (``bench/configs/<name>.json``), whose models are
  ``bench/models/<model>.json`` (published HF ``config.json`` numbers)
  and whose plain reference is ``bench/reference/<reference>.py``;
- an architecture: ``bench/families/<family>.py``, named by the
  ``family`` key of each model file (:func:`family_module`);
- a traffic mix: ``bench/traffic/<traffic>.json``, read by the one
  generator in :mod:`bench.traffic`;
- a metric: ``bench/metrics/<name>.py``, or, for a metric split by
  traffic (``tokens_per_pass.code``), ``bench/metrics/<base>.py``.

Adding a cell, configuration, mix or metric is adding files and entries.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import sys
from pathlib import Path
from types import ModuleType

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class SpecError(ValueError):
    """A cell, configuration or traffic file is missing or malformed."""


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: dict
    traffic: dict
    target: object            # its family's Dims
    draft: object             # the target itself for a self-drafting pair
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


def _model_file(name: str) -> dict:
    path = BENCH / "models" / f"{name}.json"
    if not path.is_file():
        raise SpecError(f"no model file {path}")
    return json.loads(path.read_text())


def load_model(name: str):
    """Model ``name``'s ``Dims``, made by the family its file names."""
    model = _model_file(name)
    if "family" not in model:
        raise SpecError(f"model file {name!r} names no family")
    return family_module(model["family"]).dims(dict(model, name=name))


def config_models(config: dict) -> tuple[object, object, bool]:
    """(target, draft, self_draft) of a configuration file: the target's
    numbers sit at the top level, and every key the configuration shares
    with the target's model file has to agree with it; the draft is a
    model file of its own (of any family) or ``self``."""
    model = _model_file(config["target_model"])
    for key in sorted(config.keys() & model.keys()):
        if config[key] != model[key]:
            raise SpecError(f"configuration {key}={config[key]!r} differs "
                            f"from the model file's {model[key]!r}")
    target = load_model(config["target_model"])
    if config["draft_model"] == "self":
        return target, target, True
    return target, load_model(config["draft_model"]), False


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


@functools.lru_cache(maxsize=None)
def _load(path: Path) -> ModuleType:
    """The module at ``path``, loaded once."""
    modname = f"bench_{path.parent.name}_{path.stem}".replace(
        ".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod      # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str) -> ModuleType:
    """The reader module of metric ``name``: ``metrics/<name>.py``, else
    ``metrics/<base>.py`` for a name split by traffic (``base.suffix``)."""
    for stem in (name, name.split(".", 1)[0]):
        path = BENCH / "metrics" / f"{stem}.py"
        if path.is_file():
            return _load(path)
    raise SpecError(f"metric {name!r}: no reader under {BENCH / 'metrics'}")


def _module(kind: str, name: str) -> ModuleType:
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"no {kind} file {path}")
    return _load(path)


def family_module(name: str) -> ModuleType:
    """The architecture file ``families/<name>.py``. It gives ``dims(model
    file's numbers) -> Dims`` (frozen and hashable, with ``family``,
    ``name``, ``source``, ``layers``, ``d_model``, ``vocab``, ``dtype``,
    ``tied``, and ``params()``, ``kv_bytes(context)``, ``pass_flops`` and
    ``pass_bytes(tokens, contexts)`` of the needed work, ``shrink(**widths)``),
    ``leaf_specs(dims)`` and ``global_specs(dims)`` (the leaves
    :mod:`bench.weights` draws), ``program_params(dims, key)`` (jitted,
    ``dims`` static) and ``program_config(dims)``."""
    return _module("families", name)


def family_of(m) -> ModuleType:
    """The family module of a model's ``Dims``."""
    return family_module(m.family)


def reference_module(config: dict) -> ModuleType:
    return _module("reference", config["reference"])
