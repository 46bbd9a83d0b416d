"""Reduction of a profiler trace (``*.xplane.pb``) to what the per-layer
metrics read.

Device planes are ``/device:TPU:<n>``; on each, the ``XLA Ops`` line holds
the executed HLO instructions (nested: a ``while`` spans its body's ops),
``Async XLA Ops`` each asynchronous instruction from its ``-start`` to its
``-done`` (a copy or a collective in flight), and ``XLA Modules`` one
event per executed program.  Host spans are the
benchmark's own ``TraceAnnotation``s, named ``bench.*``.  Host and device
events share one clock in the trace.

Pallas calls carry no kernel name in the trace: each is a ``custom-call``
instruction named after the jitted wrapper that launched it, and its
kind is read from its results and operands.  Both are the kernel
family's (``perfbench/kernels/<family>.py``: ``WRAPPER`` and ``kind``).
"""
from __future__ import annotations

import bisect
import collections
import functools
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

from perfbench import registry

Interval = Tuple[int, int]

_TYPE = re.compile(r"\b(bf16|f32|f16|s32|u32|pred)\[([0-9,]*)\]")
_COLLECTIVE = re.compile(r"(all-reduce|all-gather|reduce-scatter|"
                         r"collective-permute|all-to-all)(-start|-done)?\b")


def find(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, "
                                f"found {files}")
    return files[0]


def load(path: str) -> Dict:
    """Device ops and modules per device, and the host's bench spans."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    devices: Dict[str, Dict[str, list]] = {}
    host: List[Tuple[int, int, str]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = {"ops": [], "async": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "Async XLA Ops": "async",
                       "XLA Modules": "modules"}.get(line.name)
                if key:
                    dev[key] = [(int(e.start_ns), int(e.end_ns), e.name)
                                for e in line.events]
            devices[plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(int(e.start_ns), int(e.end_ns), e.name)
                         for e in line.events if e.name.startswith("bench.")]
    return {"devices": devices, "host": sorted(host)}


def clip(ivs, lo, hi) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b, *_ in ivs
            if min(b, hi) > max(a, lo)]


def union(ivs) -> List[Interval]:
    out: List[List[int]] = []
    for a, b in sorted(ivs):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [tuple(x) for x in out]


def total(ivs) -> int:
    return sum(b - a for a, b in ivs)


def gaps(busy: List[Interval], lo: int, hi: int) -> List[Interval]:
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def _nest(ops):
    """Nested ops sorted by start, each with the intervals of the ops it
    contains directly."""
    ops = sorted(ops, key=lambda e: (e[0], -e[1]))
    kids: List[List[Interval]] = [[] for _ in ops]
    stack: List[int] = []
    for i, (a, b, _) in enumerate(ops):
        while stack and ops[stack[-1]][1] <= a:
            stack.pop()
        if stack:
            kids[stack[-1]].append((a, b))
        stack.append(i)
    return zip(ops, kids)


def self_times(ops) -> List[Tuple[int, int, str, int]]:
    """(start, end, name, self ns) of nested ops: each op's time less the
    time of the ops it contains."""
    return [(a, b, n, (b - a) - total(k)) for (a, b, n), k in _nest(ops)]


def self_pieces(ops) -> List[Tuple[int, int, str]]:
    """The pieces of time of nested ops in which each runs itself: its
    interval less those of the ops it contains."""
    return [(p, q, n) for (a, b, n), k in _nest(ops)
            for p, q in (gaps(union(k), a, b) if k else [(a, b)])]


@functools.lru_cache(maxsize=None)
def is_collective(name: str) -> bool:
    """Whether an instruction exchanges data between devices (an
    all-reduce, all-gather, reduce-scatter, collective-permute or
    all-to-all, its ``-start`` and ``-done`` included)."""
    instr, _, rest = name.partition(" = ")
    return bool(_COLLECTIVE.match(instr.lstrip("%"))
                or re.search(_COLLECTIVE.pattern + r"\(", rest))


def in_steps(dev: Dict, mods: List[Interval]) -> Dict[str, int]:
    """ns of one device inside its step programs ``mods``: their length,
    the time in which no op runs nor is in flight (``idle``), and the
    time in which collectives run or are in flight and no other op runs
    itself (``exposed_collective``)."""
    starts = [a for a, _ in mods]

    def inside(events):
        """Each event that starts in a step program, cut to its end."""
        out = []
        for a, b, n in events:
            i = bisect.bisect_right(starts, a) - 1
            if i >= 0 and a < mods[i][1]:
                out.append((a, min(b, mods[i][1]), n))
        return out

    ops, flight = inside(dev["ops"]), inside(dev["async"])
    pieces = self_pieces(ops)
    other = union((a, b) for a, b, n in pieces if not is_collective(n))
    coll = union([(a, b) for a, b, n in pieces + flight if is_collective(n)])
    running = union((a, b) for a, b, _ in ops + flight)
    return {"module": total(mods), "idle": total(mods) - total(running),
            "exposed_collective": total(union(coll + other)) - total(other)}


def short(name: str) -> str:
    """``%fusion.12 = bf16[2,4096]{...} fusion(...)`` -> ``fusion.12
    bf16[2,4096]``."""
    instr, _, rest = name.partition(" = ")
    m = _TYPE.search(rest)
    return instr.lstrip("%") + (f" {m.group(0)}" if m else "")


def kernel_kind(name: str) -> Optional[str]:
    """The kind of a Pallas call from its instruction, or None: the kernel
    family whose ``WRAPPER`` launched it tells it from the (dtype, dims)
    of its results and the text of its operands."""
    instr, _, rest = name.partition(" = ")
    if "custom-call(" not in rest:
        return None
    result, _, operands = rest.partition(" custom-call(")
    for family in registry.kernel_families():
        if instr.startswith("%" + family.WRAPPER):
            return family.kind(_TYPE.findall(result), operands)
    return None


def summarize(trace: Dict, lo: int, hi: int, step_depths: List,
              top: int = 10) -> Dict:
    """Busy time, per-step device time, each device's time in its step
    programs (:func:`in_steps`), kernel calls and the breakdown of the
    traced window [lo, hi] (ns)."""
    busy_ns, step_ns, kernels = [], [], collections.defaultdict(
        lambda: [0, 0])
    op_self = collections.Counter()
    gap_list = []
    steps_in = {}
    host = trace["host"]
    for dev_name, dev in sorted(trace["devices"].items()):
        ops = clip(dev["ops"], lo, hi)
        busy = union(ops)
        busy_ns.append(total(busy))
        mods = [(a, b) for a, b, n in dev["modules"]
                if n.startswith("jit_step(") and a >= lo and b <= hi]
        step_ns.append([b - a for a, b in mods])
        steps_in[dev_name] = {k: v / 1e9 for k, v in in_steps(dev, mods)
                              .items()}
        named = [(a, b, n) for a, b, n in dev["ops"] if a >= lo and b <= hi]
        for a, b, n, self_ns in self_times(named):
            kind = kernel_kind(n)
            if kind:
                kernels[kind][0] += 1
                kernels[kind][1] += b - a
            depth = _depth_at(a, mods, step_depths)
            op_self[f"d{depth}:{short(n)}" if depth is not None
                    else short(n)] += self_ns
        if dev_name.endswith(":0"):
            for a, b in gaps(busy, lo, hi):
                gap_list.append((b - a, _host_label(a, b, host)))
    n_dev = max(len(busy_ns), 1)
    gap_list.sort(reverse=True)
    return {
        "devices": len(busy_ns),
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy_ns) / n_dev / 1e9,
        "step_device_s": [[x / 1e9 for x in s] for s in step_ns],
        "in_steps_s": steps_in,
        "kernels": {k: {"calls": v[0], "seconds": v[1] / 1e9}
                    for k, v in sorted(kernels.items())},
        "device_ops": [[n, s / 1e9 / n_dev]
                       for n, s in op_self.most_common(top)],
        "idle_gaps": [[label, ns / 1e9] for ns, label in gap_list[:top]],
    }


def _depth_at(t, mods, step_depths):
    """The depth of the step program (of ``mods``, sorted and disjoint)
    that runs at ``t``, or None."""
    i = bisect.bisect_right(mods, (t, float("inf"))) - 1
    if i >= 0 and t < mods[i][1]:
        return step_depths[i] if i < len(step_depths) else None
    return None


def _host_label(a: int, b: int, host) -> str:
    """The innermost bench span that overlaps the gap the most."""
    best, best_ns = "no host span", 0
    for ha, hb, n in host:
        if n == "bench.window":
            continue
        ov = min(b, hb) - max(a, ha)
        if ov > best_ns:
            best, best_ns = n, ov
    return best
