"""The program's spans and counters, on the profiler's clock.

Every span is a ``jax.profiler.TraceAnnotation``: while a profiler trace is
taken its events land in the trace's host plane, on the clock of the device
planes, so an idle gap on the device can be put down to what the host was
doing.  With no trace active a span costs about two microseconds.  Names
come from :data:`SPANS` only; none starts with ``bench.``, the prefix of
the benchmark's own spans.

The counters are process-wide by nature: JAX's monitoring listeners and
Python's GC callbacks belong to the process, so :func:`install` hooks them
once, whichever session asks first.
"""
from __future__ import annotations

import gc
import threading
from typing import Dict

import jax

#: Every span the program opens, and where.
SPANS = {
    "data.get_batch": "Pipeline.get_batch, the whole call; stat step",
    "data.sample": "MarkovLM.sample inside data.get_batch",
    "engine.train_step": "SPBEngine.train_step, entry to return; stats "
                         "step, depth (the table key), compiles",
    "engine.enqueue": "the step program's call inside engine.train_step",
    "host.gc": "one Python garbage collection; stat generation",
}

#: Recorded around every XLA compile request of the process.
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_lock = threading.Lock()
_counters = {"compiles": 0}
_installed = False
_gc_spans: list = []


def span(name: str, **stats) -> jax.profiler.TraceAnnotation:
    """A context manager for the span ``name`` of :data:`SPANS`; ``stats``
    are recorded with it (``set_metadata`` adds more before it closes)."""
    if name not in SPANS:
        raise KeyError(f"unknown span {name!r}; known: {sorted(SPANS)}")
    return jax.profiler.TraceAnnotation(name, **stats)


def counters() -> Dict[str, int]:
    """A snapshot of the process counters: ``compiles``."""
    with _lock:
        return dict(_counters)


def _on_duration(event: str, duration_s: float, **_) -> None:
    if event == COMPILE_EVENT:
        with _lock:
            _counters["compiles"] += 1


def _on_gc(phase: str, info: dict) -> None:
    if phase == "start":
        s = span("host.gc", generation=info["generation"])
        s.__enter__()
        _gc_spans.append(s)
    elif _gc_spans:
        _gc_spans.pop().__exit__(None, None, None)


def install() -> None:
    """Hook the counters and the GC span into the process, once.

    ``compiles`` counts :data:`COMPILE_EVENT`, which JAX records for each
    executable it builds or loads: a call served from a jit's in-memory
    cache adds nothing, a hit in the persistent compilation cache adds one
    (checked on the CPU with jax 0.9.0), since it too holds the host.
    Every Python garbage collection becomes a ``host.gc`` span.
    """
    global _installed
    with _lock:
        if _installed:
            return
        _installed = True
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    gc.callbacks.append(_on_gc)
