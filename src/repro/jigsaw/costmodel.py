"""Task cost database for the scheduler: per-model fwd/bwd time, memory,
model size — and their scaling under SPB partial backprop.

Two sources:
  * The paper's own V100 profiles (Table 2) — used to reproduce Fig 4 on
    the same workload the paper simulated.
  * HLO-derived TPU profiles of this repo's 10 architectures (from
    results/dryrun/*.json): step time estimated as the max of the three
    roofline terms — the beyond-paper link where the simulator schedules
    jobs whose costs come from the real compiled programs.

SPB scaling (paper Table 1, measured linear):
  time(frac) = fwd + frac * bwd
  mem(frac)  = mem_fwd + frac * (mem_peak - mem_fwd)
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, Optional

# --- Paper Table 2 (V100, batch 128): times ms, mem GB, grad MB ---
V100_PROFILES = {
    # name: (fwd_ms, fwd_mem, bwd_ms, bwd_mem, grad_mb)
    "resnet18": (9.19, 0.05, 21.49, 2.46, 44),
    "resnet34": (16.11, 0.08, 36.69, 3.08, 85),
    "resnet50": (36.32, 0.09, 78.9, 7.33, 94),
    "resnet101": (60.51, 0.17, 135.14, 9.79, 170),
    "resnet152": (86.9, 0.23, 197.05, 12.81, 232),
    "vgg19": (6.82, 0.08, 16.31, 2.02, 80),
    "vgg16": (5.68, 0.06, 13.96, 1.97, 59),
    "vgg11": (3.34, 0.04, 7.8, 1.83, 36),
    "googlenet": (41.33, 0.05, 99.17, 5.96, 24),
}


@dataclass
class ModelProfile:
    name: str
    fwd_s: float
    bwd_s: float
    mem_fwd_gb: float
    mem_peak_gb: float
    model_size_gb: float
    grad_gb: float

    def task_time(self, spb_fraction: float) -> float:
        return self.fwd_s + spb_fraction * self.bwd_s

    def task_mem(self, spb_fraction: float) -> float:
        return self.mem_fwd_gb + spb_fraction * (
            self.mem_peak_gb - self.mem_fwd_gb)

    def grad_bytes(self, spb_fraction: float) -> float:
        return self.grad_gb * 2 ** 30 * spb_fraction


def v100_profiles() -> Dict[str, ModelProfile]:
    out = {}
    for name, (f_ms, f_gb, b_ms, b_gb, g_mb) in V100_PROFILES.items():
        out[name] = ModelProfile(
            name=name, fwd_s=f_ms / 1e3, bwd_s=b_ms / 1e3,
            mem_fwd_gb=f_gb + 0.5,               # + weights/workspace floor
            mem_peak_gb=f_gb + b_gb + 0.5,
            model_size_gb=g_mb / 1024.0,         # params ~ grad size
            grad_gb=g_mb / 1024.0)
    return out


def hlo_profiles(device_kind: str, results_dir: Optional[Path] = None,
                 shape: str = "train_4k") -> Dict[str, ModelProfile]:
    """Per-arch profiles from the dry-run JSONs (per-device roofline),
    priced at the published peaks of ``device_kind``."""
    from repro.analysis.roofline import peaks
    pk = peaks(device_kind)
    if results_dir is None:
        results_dir = Path(__file__).resolve().parents[3] / "results" / "dryrun"
    out = {}
    if not results_dir.exists():
        return out
    for p in sorted(results_dir.glob(f"*__{shape}__pod16x16.json")):
        rec = json.loads(p.read_text())
        if not rec.get("ok"):
            continue
        flops = rec["flops_per_device"]
        byts = rec["bytes_per_device"]
        coll = rec["collective_bytes_per_device"]
        step = max(flops / pk.flops, byts / pk.hbm_bw, coll / pk.link_bw)
        ma = rec.get("memory_analysis", {})
        temp = ma.get("temp_size_in_bytes", 8 * 2 ** 30) / 2 ** 30
        args = ma.get("argument_size_in_bytes", 4 * 2 ** 30) / 2 ** 30
        # assume bwd is ~2/3 of a train step (fwd:bwd ~ 1:2)
        out[rec["arch"]] = ModelProfile(
            name=rec["arch"], fwd_s=step / 3, bwd_s=2 * step / 3,
            mem_fwd_gb=min(args, 8.0), mem_peak_gb=min(args + temp, 16.0),
            model_size_gb=min(args, 8.0), grad_gb=min(args / 3, 4.0))
    return out


def profile_db(hlo_device_kind: Optional[str] = None
               ) -> Dict[str, ModelProfile]:
    """The paper's V100 profiles, plus the dry-run-derived ones priced for
    ``hlo_device_kind`` when a kind is named."""
    db = v100_profiles()
    if hlo_device_kind is not None:
        db.update(hlo_profiles(hlo_device_kind))
    return db


def spb_worker_fractions(num_workers: int, k: Optional[int] = None) -> List[float]:
    """Paper worker assignment: worker j of k backprops (j+1)/k of layers."""
    k = k or num_workers
    return [min(1.0, math.ceil((j % k + 1) * k / k) / k * 1.0)
            if False else (j % k + 1) / k
            for j in range(num_workers)]
