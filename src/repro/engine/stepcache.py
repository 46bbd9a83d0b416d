"""Cross-job compiled-step sharing: one process-wide step table.

Before this module, every ``SPBEngine`` in a pool owned a private jitted
step table, so N same-config tenant jobs paid N identical traces +
compiles during warmup — pool warmup scaled with *job count*.  The fix
is one process-wide table keyed on everything that determines the
compiled program:

    (model config, train config*, SPB config, zero1, donate,
     parallelism, pipeline schedule/data, depth key, mesh fingerprint)

``train config*`` drops the knobs that never reach the compiled step
(checkpoint/log cadence, and the seed when compression is off — the
same scrub :func:`repro.engine.aot.cache_key` applies), so two tenants
that differ only by data seed share every entry.  The mesh fingerprint
includes concrete device ids: engines on the *same* submesh share
wrappers; engines on disjoint submeshes get distinct entries (an
executable is placed on specific devices).

Sharing jit *wrappers* (not executables) is what makes this safe:
``jax.jit`` caches compiled executables per argument-shape under the
wrapper, donation is per-call (each engine donates its own state
buffers), and the wrapper itself carries no session state.

Two engines, one entry — warmup scales with distinct step shapes:

>>> from repro.config import SPBConfig, TrainConfig
>>> from repro.configs import reduced_config
>>> from repro.engine import SPBEngine
>>> from repro.engine import stepcache
>>> stepcache.GLOBAL.clear()
>>> cfg = reduced_config("yi-6b")
>>> a = SPBEngine(cfg, TrainConfig(seed=0), SPBConfig(mode="temporal", k=2))
>>> b = SPBEngine(cfg, TrainConfig(seed=1), SPBConfig(mode="temporal", k=2))
>>> a.step_fn(2) is b.step_fn(2)      # same wrapper object, one trace
True
>>> stepcache.GLOBAL.stats()["entries"]
1
>>> stepcache.GLOBAL.stats()["hits"]
1

This module also turns on jax's *persistent* compilation cache (the
on-disk XLA-level cache, :func:`enable_compilation_cache`), which
dedupes compiles across *processes* the way :data:`GLOBAL` dedupes
traces within one.
"""
from __future__ import annotations

import os
import threading
from pathlib import Path
from typing import Any, Callable, Dict, Optional, Tuple


class StepCache:
    """A thread-safe ``key -> jitted step`` table with hit/miss stats.

    ``get_or_build`` runs ``builder`` outside the lock (building a jit
    wrapper is cheap but tracing under a lock would serialize unrelated
    engines); a concurrent duplicate build resolves to whichever entry
    landed first, counted as a hit for the loser.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._entries: Dict[Any, Callable] = {}
        self.hits = 0
        self.misses = 0

    def get_or_build(self, key: Any, builder: Callable[[], Callable]):
        with self._lock:
            fn = self._entries.get(key)
            if fn is not None:
                self.hits += 1
                return fn
        built = builder()
        with self._lock:
            fn = self._entries.setdefault(key, built)
            if fn is built:
                self.misses += 1
            else:
                self.hits += 1
            return fn

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {"hits": self.hits, "misses": self.misses,
                    "entries": len(self._entries)}

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self.hits = 0
            self.misses = 0

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)


#: The process-wide table every ``SPBEngine(shared_cache=True)`` consults.
GLOBAL = StepCache()


def mesh_fingerprint(mesh) -> Tuple:
    """Hashable identity of a mesh *placement*: axis names, shape, and
    the concrete device ids.  Two mesh objects over the same devices in
    the same layout fingerprint equal (a re-built submesh re-hits the
    cache); disjoint submeshes never collide."""
    return (tuple(mesh.axis_names),
            tuple(int(s) for s in mesh.devices.shape),
            tuple(int(d.id) for d in mesh.devices.flat))


# -- jax persistent compilation cache (cross-process) ----------------------

#: Where the cache lives when ``JAX_COMPILATION_CACHE_DIR`` is not set: one
#: fixed directory at the root of the checkout (listed in .gitignore).  A
#: fixed path, so that a later process finds what an earlier one compiled.
DEFAULT_COMPILATION_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compilation_cache() -> Path:
    """Turn on jax's on-disk XLA compilation cache, placed from outside.

    With ``JAX_COMPILATION_CACHE_DIR`` set, jax already reads that
    directory and no other is set here; without it the cache goes to
    :data:`DEFAULT_COMPILATION_CACHE`.  The size and compile-time
    thresholds are dropped so that every compile is eligible.  Returns the
    directory in use.  Entry points call this first; importing this
    module changes nothing.
    """
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        path = Path(env)
    else:
        path = DEFAULT_COMPILATION_CACHE
        jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
