"""Tiny stand-ins of the benchmark's cells for the CPU tests: the real
cell's files with every width shrunk, in a scratch checkout root."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

from perfbench import registry

REPO = registry.ROOT


def shrink(hf):
    """A configuration file's stand-in: its architecture's ``STAND_IN``
    widths (published keys, a group merged key by key) and trainer
    overrides."""
    published, overrides = registry.arch(hf["model_type"]).STAND_IN
    hf = dict(hf)
    for k, v in published.items():
        hf[k] = dict(hf.get(k, {}), **v) if isinstance(v, dict) else v
    hf["trainer"] = dict(hf["trainer"], overrides=dict(
        hf["trainer"]["overrides"], **overrides))
    return hf


def make_root(tmp: Path, workload: str, seq_len: int = 64) -> Path:
    """A checkout root holding one tiny cell named ``workload`` (with the
    real cell's traffic, limits and metrics), its file layout the real
    one's."""
    tmp = Path(tmp)
    real = registry.cell(workload, REPO)
    bench = registry.benchmark(REPO)
    pb = tmp / registry.HERE
    for d in ("configs", "traffic", "limits"):
        (pb / d).mkdir(parents=True, exist_ok=True)
    shutil.copytree(REPO / registry.HERE / "metrics", pb / "metrics",
                    dirs_exist_ok=True)
    entry = next(c for c in bench["configs"] if c["name"] == real.config_name)
    hf = shrink(json.loads((REPO / entry["file"]).read_text()))
    (tmp / entry["file"]).write_text(json.dumps(hf))
    job = dict(real.job, seq_len=seq_len)
    (pb / "traffic" / f"{real.traffic_name}.json").write_text(json.dumps(job))
    shutil.copy(REPO / registry.HERE / "limits" / f"{workload}.json",
                pb / "limits" / f"{workload}.json")
    bench["configs"] = [entry]
    bench["workloads"] = [w for w in bench["workloads"]
                          if w["name"] == workload]
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp
