"""Finds everything of a benchmark cell by its name in ``BENCHMARK.json``.

Each piece is a file of its own, found by name, so that a later change
adds a configuration, a traffic mix, a cell or a per-layer metric by adding
files and entries:

* ``BENCHMARK.json`` ``configs[].file``: the configuration (published
  keys as run, the trainer's architecture and overrides, departures);
* ``perfbench/traffic/<traffic>.json``: the job (batch, sequence length,
  SPB mode and k, parallelism, microbatches, optimizer; a pipeline job
  also its ``mesh`` of ``stage``, ``data`` and ``model`` sizes and its
  ``schedule``);
* ``perfbench/limits/<workload>.json``: the limits of the correctness
  comparison, with the readings they were set from;
* ``perfbench/metrics/<metric>.py``: the reader of one per-layer metric,
  a ``read(record)`` that returns a number or None.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

ROOT = Path(__file__).resolve().parent.parent
HERE = "perfbench"


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    hf: Dict[str, Any]           # the configuration file
    job: Dict[str, Any]          # the traffic file
    limits: Optional[Dict[str, Any]]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


#: Published key names that mean what the Llama-style names the benchmark
#: reads mean (mamba_ssm's config.json uses the former).
ALIASES = {"d_model": "hidden_size", "n_layer": "num_hidden_layers",
           "tie_embeddings": "tie_word_embeddings",
           "norm_epsilon": "rms_norm_eps"}


def canonical(hf: Dict[str, Any]) -> Dict[str, Any]:
    """A configuration file with the Llama-style names added."""
    out = dict(hf)
    for published, name in ALIASES.items():
        if published in hf:
            out.setdefault(name, hf[published])
    return out


def benchmark(root: Path = ROOT) -> Dict[str, Any]:
    with open(Path(root) / "BENCHMARK.json") as f:
        return json.load(f)


def _json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def _reports(metric: Dict[str, Any], workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` with its configuration, job, limits and metrics;
    raises KeyError or FileNotFoundError for anything missing."""
    root = Path(root)
    bench = benchmark(root)
    wl = {w["name"]: w for w in bench["workloads"]}
    if name not in wl:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(wl)}")
    w = wl[name]
    configs = {c["name"]: c for c in bench["configs"]}
    hf = canonical(_json(root / configs[w["config"]]["file"]))
    job = _json(root / HERE / "traffic" / f"{w['traffic']}.json")
    lim_path = root / HERE / "limits" / f"{name}.json"
    limits = _json(lim_path) if lim_path.exists() else None
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                traffic_name=w["traffic"], hf=hf, job=job, limits=limits,
                end_to_end=[m for m in bench["end_to_end"]
                            if _reports(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _reports(m, name)])


def reader(metric: str, root: Path = ROOT) -> Callable[[Dict], Any]:
    """The ``read`` function of ``perfbench/metrics/<metric>.py``."""
    path = Path(root) / HERE / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"perfbench_metric_{metric.replace('.', '_')}", path)
    if spec is None or not path.exists():
        raise FileNotFoundError(f"no reader for metric {metric!r} at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
