"""Arithmetic shared by the per-layer metric readers in ``metrics/``."""
from __future__ import annotations

import statistics
from typing import Dict, Optional

from perfbench import counts


def step_device_ms(rec: Dict, depth: int) -> Optional[float]:
    """Median device time (ms) of the window's step programs at ``depth``:
    each step's ``jit_step`` module in the trace, matched to the steps in
    order."""
    tr = rec.get("trace")
    if not tr or not tr["step_device_s"]:
        return None
    depths = [s["depth"] for s in rec["steps"]]
    xs = []
    for dev in tr["step_device_s"]:
        if len(dev) != len(depths):      # the trace lost or added a step
            return None
        xs += [t for t, d in zip(dev, depths) if d == depth]
    return statistics.median(xs) * 1e3 if xs else None


def roofline_share(rec: Dict, prefix: str) -> Optional[float]:
    """The least time the chip could take for every call of a kernel
    family in the traced window (each call's larger of flops over the peak
    and bytes over the HBM bandwidth, ``counts.kernel_cost``), over the
    calls' measured device time, in %."""
    tr = rec.get("trace")
    if not tr or not rec.get("peaks"):
        return None
    pk = rec["peaks"]
    ideal = seconds = 0.0
    for kind, k in tr["kernels"].items():
        if not kind.startswith(prefix) or not k["calls"]:
            continue
        c = counts.kernel_cost(rec["hf"], rec["job"], kind)
        ideal += k["calls"] * max(c["flops"] / pk["flops"],
                                  c["bytes"] / pk["hbm_bytes_per_s"])
        seconds += k["seconds"]
    return 100.0 * ideal / seconds if seconds else None
