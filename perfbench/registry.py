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
  a ``read(record)`` that returns a number or None;
* ``perfbench/archs/<model_type>.py``: one architecture, for the
  configurations of its ``model_type``: ``layer_spec(hf)``, the weight
  layout of one layer (path -> (shape, dtype, init rule), a rule being a
  name that ``weights.py`` knows or a function (key, shape) -> float32
  values); ``layer(p, x, hf, low)``, one layer of the float32 reference,
  and optionally ``stack(params, x, hf, low, boundary)``, the whole stack
  where its layers differ by kind (default ``reference.scan_layers``);
  ``layer_flops(hf, job)``, one layer's forward and entry-projection
  flops; ``program_keys(hf)``, the trainer's config attributes it checks
  (dots descend into a group) with the file's values; ``STAND_IN``, the
  CPU stand-in's (published-key updates, trainer overrides); optionally
  ``ALIASES``, published key names that mean the Llama-style names the
  benchmark reads;
* ``perfbench/kernels/<family>.py``: one family of Pallas calls, whose
  kinds are named ``<family>_<what>`` (a family's name has no ``_``):
  ``WRAPPER``, the jitted wrapper that launches them, as the trace names
  the call; ``KINDS``; ``kind(results, operands)``, a call's kind (or
  None) from its results' (dtype, dims) pairs and its operands' text;
  ``cost(hf, job, kind)``, one call's flops and bytes.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Tuple

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


def canonical(hf: Dict[str, Any]) -> Dict[str, Any]:
    """A configuration file with the Llama-style names added (its
    architecture's ``ALIASES``)."""
    out = dict(hf)
    for published, name in getattr(arch(hf["model_type"]), "ALIASES",
                                   {}).items():
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


@functools.lru_cache(maxsize=None)
def _module(path: Path, label: str):
    spec = importlib.util.spec_from_file_location(label, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str, root: Path = ROOT) -> Callable[[Dict], Any]:
    """The ``read`` function of ``perfbench/metrics/<metric>.py``."""
    path = Path(root) / HERE / "metrics" / f"{metric}.py"
    if not path.exists():
        raise FileNotFoundError(f"no reader for metric {metric!r} at {path}")
    return _module(path, f"perfbench_metric_{metric.replace('.', '_')}").read


def arch(model_type: str, root: Path = ROOT):
    """The module ``perfbench/archs/<model_type>.py``."""
    path = Path(root) / HERE / "archs" / f"{model_type}.py"
    if not path.exists():
        raise FileNotFoundError(f"no architecture for model_type "
                                f"{model_type!r}: no file {path}")
    return _module(path, f"perfbench_arch_{model_type}")


def kernel_family(kind: str, root: Path = ROOT):
    """The module ``perfbench/kernels/<family>.py`` of a kernel kind
    ``<family>_<what>``."""
    family = kind.partition("_")[0]
    path = Path(root) / HERE / "kernels" / f"{family}.py"
    if not path.exists():
        raise FileNotFoundError(f"no kernel family for kind {kind!r}: no "
                                f"file {path}")
    return _module(path, f"perfbench_kernel_{family}")


@functools.lru_cache(maxsize=None)
def kernel_families(root: Path = ROOT) -> Tuple[Any, ...]:
    """Every module under ``perfbench/kernels/``, by name."""
    return tuple(_module(p, f"perfbench_kernel_{p.stem}") for p in
                 sorted((Path(root) / HERE / "kernels").glob("*.py")))
