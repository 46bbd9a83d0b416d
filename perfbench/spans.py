"""The program's own spans in the trace that the harness has just written.

``tracefile.load`` keeps only the benchmark's ``bench.*`` host spans.  The
program opens spans of its own (``src/repro/obs.py``): ``data.get_batch``
and ``data.sample`` in its data layer, ``engine.train_step`` (stats
``step``, ``depth``, ``compiles``) and ``engine.enqueue`` in its engine,
``host.gc`` around each Python garbage collection.  The readers of
``metrics/`` get only the record, so this module finds the trace itself:
``rec["trace_path"]`` (a trace directory or ``.xplane.pb`` file) where the
record names one, else the harness's default directory.  Each file is
parsed once.  A program without these spans gives no window here, and
every reader built on it returns None.
"""
from __future__ import annotations

import functools
import os
import statistics
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from perfbench import registry, tracefile

#: The program's span names (``repro.obs.SPANS``).
NAMES = ("data.get_batch", "data.sample", "engine.train_step",
         "engine.enqueue", "host.gc")
DEFAULT_DIR = registry.ROOT / "perfbench_out" / "trace"

#: (host plane and line, start ns, end ns, stats)
Span = Tuple[Tuple[str, int], int, int, Dict]


def trace_file(rec: Dict) -> Optional[str]:
    path = Path(rec.get("trace_path") or DEFAULT_DIR)
    if path.is_file():
        return str(path)
    try:
        return tracefile.find(str(path))
    except FileNotFoundError:
        return None


@functools.lru_cache(maxsize=4)
def _parse(path: str, mtime_ns: int, size: int) -> Dict:
    """The program's spans, the ``bench.window`` spans and the union of the
    device ops on ``/device:TPU:0`` (None without that plane)."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    spans: Dict[str, List[Span]] = {n: [] for n in NAMES}
    windows, busy = [], None
    for plane in pd.planes:
        if plane.name == "/device:TPU:0":
            busy = tracefile.union(
                (int(e.start_ns), int(e.end_ns)) for line in plane.lines
                if line.name == "XLA Ops" for e in line.events)
        elif plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                for e in line.events:
                    if e.name in spans:
                        spans[e.name].append(
                            ((plane.name, i), int(e.start_ns), int(e.end_ns),
                             dict(e.stats)))
                    elif e.name == "bench.window":
                        windows.append((int(e.start_ns), int(e.end_ns)))
    return {"spans": spans, "windows": windows, "busy": busy}


def window(rec: Dict) -> Optional[Dict]:
    """The program's spans that overlap the traced window, cut to it (the
    window holds whole steps, so only a GC can be cut), the window's
    bounds ``lo``, ``hi`` (ns) and the device's busy intervals in it (None
    without a TPU plane).  None where there is no trace, where its window
    is not the record's, or where the window holds no engine step span."""
    path = trace_file(rec)
    if path is None:
        return None
    st = os.stat(path)
    tr = _parse(path, st.st_mtime_ns, st.st_size)
    if len(tr["windows"]) != 1:
        return None
    lo, hi = tr["windows"][0]
    summary = rec.get("trace")
    if summary and abs((hi - lo) / 1e9 - summary["window_s"]) > 1e-9:
        return None                 # a trace left by another run
    spans = {n: [(where, max(a, lo), min(b, hi), stats)
                 for where, a, b, stats in v if min(b, hi) > max(a, lo)]
             for n, v in tr["spans"].items()}
    if not spans["engine.train_step"]:
        return None
    busy = None if tr["busy"] is None else tracefile.clip(tr["busy"], lo, hi)
    return {"lo": lo, "hi": hi, "spans": spans, "busy": busy}


def _inside(child: Span, parent: Span) -> bool:
    return (child[0] == parent[0] and parent[1] <= child[1]
            and child[2] <= parent[2])


def per_step(w: Dict, parent: str, child: str) -> Dict[int, Tuple[int, int]]:
    """{step stat: (ns of the ``parent`` span, ns of its ``child`` spans)}
    in the window."""
    out = {}
    for p in w["spans"][parent]:
        kids = sum(c[2] - c[1] for c in w["spans"][child] if _inside(c, p))
        out[p[3]["step"]] = (p[2] - p[1], kids)
    return out


def median_ms(xs) -> Optional[float]:
    xs = list(xs)
    return statistics.median(xs) / 1e6 if xs else None


def idle_gaps(w: Dict) -> List[Tuple[int, int]]:
    """The device's idle intervals in the window."""
    return tracefile.gaps(w["busy"], w["lo"], w["hi"])


def idle_under(w: Dict, prefix: str) -> int:
    """Device idle ns in the window whose innermost overlapping program
    span (the latest-started of those covering it) is named ``prefix``*."""
    every = [(s[1], s[2], n) for n, v in w["spans"].items() for s in v]
    total = 0
    for a, b in idle_gaps(w):
        over = [s for s in every if s[0] < b and s[1] > a]
        cuts = sorted({a, b} | {t for s in over for t in s[:2] if a < t < b})
        for p, q in zip(cuts, cuts[1:]):
            cover = [s for s in over if s[0] <= p and s[1] >= q]
            if cover and max(cover, key=lambda s: (s[0], -s[1]))[2] \
                    .startswith(prefix):
                total += q - p
    return total
