"""Arithmetic shared by the per-layer metric readers in ``metrics/``."""
from __future__ import annotations

import statistics
from typing import Dict, List, Optional

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


def _stages(rec: Dict) -> Optional[Dict[int, Dict[str, float]]]:
    """{stage: seconds in the step programs (``tracefile.in_steps``) of
    its devices, summed, with their count ``devices``} of a pipeline
    cell's traced window; None for a cell without stages or a trace."""
    tr, where = rec.get("trace"), rec.get("stage_of")
    if not tr or not where or not tr.get("in_steps_s"):
        return None
    out: Dict[int, Dict[str, float]] = {}
    for plane, t in tr["in_steps_s"].items():
        s = out.setdefault(where[int(plane.rsplit(":", 1)[1])],
                           {"devices": 0})
        s["devices"] += 1
        for k, v in t.items():
            s[k] = s.get(k, 0.0) + v
    return out


def pipeline_share(rec: Dict, key: str) -> Optional[float]:
    """Every device's ``key`` seconds inside its step programs, summed,
    over those programs' seconds summed, in %."""
    st = _stages(rec)
    module = sum(s["module"] for s in st.values()) if st else 0.0
    return 100.0 * sum(s[key] for s in st.values()) / module if module \
        else None


def stage_split(rec: Dict) -> Optional[List]:
    """[name, seconds] a device of each stage spends in its step programs,
    idle in them and in exposed collectives, for the result's
    breakdown."""
    st = _stages(rec)
    if not st:
        return None
    return [[f"stage {s} {k.replace('_', ' ')}", t[k] / t["devices"]]
            for s, t in sorted(st.items())
            for k in ("module", "idle", "exposed_collective")]
