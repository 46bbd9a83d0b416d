"""Render EXPERIMENTS.md tables from the dry-run result cache.

  python -m repro.analysis.report roofline        # full §Roofline table
  python -m repro.analysis.report dryrun          # §Dry-run summary
  python -m repro.analysis.report perf            # §Perf variant deltas
  python -m repro.analysis.report spb             # SPB depth sweeps
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

from repro.analysis.roofline import (DRYRUN_DEVICE_KIND, RESULTS,
                                     full_table, load_record, roofline_row)
from repro.configs import get_config


def md_roofline(mesh: str = "pod16x16") -> str:
    rows = full_table(DRYRUN_DEVICE_KIND, mesh)
    out = ["| arch | shape | chips | compute (s) | memory (s) | collective (s) "
           "| bound | MFU | useful ratio | what moves the bound |",
           "|---|---|---:|---:|---:|---:|---|---:|---:|---|"]
    advice = {
        ("memory", "train"): "less HBM traffic: fused norms/attn, bf16 streams, remat policy",
        ("memory", "prefill"): "flash-attention kernel traffic (Pallas path) + bf16 streams",
        ("memory", "decode"): "KV-cache reads dominate: quantized KV / wider batching",
        ("collective", "train"): "TP activation all-reduces: seq-parallel sharding + bf16 reduce",
        ("collective", "prefill"): "same (TP all-reduces over long activations)",
        ("compute", "train"): "near roofline: raise MXU utilization (larger tiles)",
    }
    for r in rows:
        kind = "train" if "train" in r.shape else (
            "prefill" if "prefill" in r.shape else "decode")
        out.append(
            f"| {r.arch} | {r.shape} | {r.chips} | {r.compute_s:.4f} | "
            f"{r.memory_s:.4f} | {r.collective_s:.4f} | {r.dominant} | "
            f"{r.mfu:.1%} | {r.useful_ratio:.2f} | "
            f"{advice.get((r.dominant, kind), '-')} |")
    return "\n".join(out)


def md_dryrun() -> str:
    out = ["| arch | shape | mesh | compile (s) | flops/dev | HBM bytes/dev "
           "| wire bytes/dev | #coll | temp GiB |",
           "|---|---|---|---:|---:|---:|---:|---:|---:|"]
    for p in sorted(RESULTS.glob("*.json")):
        rec = json.loads(p.read_text())
        if not rec.get("ok") or rec.get("tag") or rec.get("depth") is not None:
            continue
        ma = rec.get("memory_analysis", {})
        out.append(
            f"| {rec['arch']} | {rec['shape']} | {rec['mesh']} | "
            f"{rec.get('compile_s', 0):.1f} | {rec['flops_per_device']:.3e} | "
            f"{rec['bytes_per_device']:.3e} | "
            f"{rec['collective_bytes_per_device']:.3e} | "
            f"{rec['num_collectives']} | "
            f"{ma.get('temp_size_in_bytes', 0)/2**30:.1f} |")
    return "\n".join(out)


def md_perf() -> str:
    """Variant (tagged) records vs their baselines."""
    out = ["| cell | variant | flops/dev | HBM bytes/dev | wire bytes/dev | "
           "temp GiB | Δbytes vs base | Δwire vs base |",
           "|---|---|---:|---:|---:|---:|---:|---:|"]
    base = {}
    tagged = []
    for p in sorted(RESULTS.glob("*.json")):
        rec = json.loads(p.read_text())
        if not rec.get("ok"):
            continue
        key = (rec["arch"], rec["shape"], rec["mesh"])
        if not rec.get("tag") and rec.get("depth") is None:
            base[key] = rec
        elif rec.get("tag"):
            tagged.append(rec)
    for rec in tagged:
        key = (rec["arch"], rec["shape"], rec["mesh"])
        b = base.get(key)
        ma = rec.get("memory_analysis", {})
        db = dw = "-"
        if b:
            db = f"{100*(rec['bytes_per_device']/b['bytes_per_device']-1):+.1f}%"
            dw = (f"{100*(rec['collective_bytes_per_device']/max(b['collective_bytes_per_device'],1)-1):+.1f}%")
        out.append(
            f"| {rec['arch']}/{rec['shape']}/{rec['mesh']} | {rec['tag']} | "
            f"{rec['flops_per_device']:.3e} | {rec['bytes_per_device']:.3e} | "
            f"{rec['collective_bytes_per_device']:.3e} | "
            f"{ma.get('temp_size_in_bytes', 0)/2**30:.1f} | {db} | {dw} |")
    return "\n".join(out)


def md_spb() -> str:
    """SPB depth-sweep records (paper Table 1 from compiled HLO)."""
    out = ["| arch | depth | flops/dev | HBM bytes/dev | wire bytes/dev | "
           "vs full flops | vs full bytes | vs full wire |",
           "|---|---:|---:|---:|---:|---:|---:|---:|"]
    by_arch = {}
    for p in sorted(RESULTS.glob("*train_4k*pod16x16*.json")):
        rec = json.loads(p.read_text())
        if not rec.get("ok") or rec.get("tag"):
            continue
        by_arch.setdefault(rec["arch"], {})[rec.get("depth")] = rec
    for arch, recs in sorted(by_arch.items()):
        full = recs.get(None)
        if full is None or len(recs) < 2:
            continue
        L = get_config(arch).num_layers
        for depth in sorted([d for d in recs if d is not None]) + [None]:
            rec = recs[depth]
            d = depth if depth is not None else L
            rf = rec["flops_per_device"] / full["flops_per_device"]
            rb = rec["bytes_per_device"] / full["bytes_per_device"]
            rw = (rec["collective_bytes_per_device"]
                  / max(full["collective_bytes_per_device"], 1))
            out.append(f"| {arch} | {d}/{L} | {rec['flops_per_device']:.3e} | "
                       f"{rec['bytes_per_device']:.3e} | "
                       f"{rec['collective_bytes_per_device']:.3e} | "
                       f"{rf:.2f}x | {rb:.2f}x | {rw:.2f}x |")
    return "\n".join(out)


if __name__ == "__main__":
    what = sys.argv[1] if len(sys.argv) > 1 else "roofline"
    print({"roofline": md_roofline, "dryrun": md_dryrun,
           "perf": md_perf, "spb": md_spb}[what]())
