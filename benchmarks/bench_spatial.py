"""Spatial co-location benchmark: shared step cache + disjoint submeshes.

Two claims, two parts, one ``BENCH_spatial.json``:

* **Warmup scales with distinct step shapes, not job count.**  Build N
  same-config tenant engines (distinct data seeds) and run their first
  step at each cycle depth.  With per-engine step tables every tenant
  pays its own trace + compile (warmup ~linear in N); with the
  process-wide :data:`repro.engine.stepcache.GLOBAL` table the first
  tenant compiles and the rest hit (warmup ~flat in N).

* **Spatial co-location beats time-multiplexing on aggregate steps/s.**
  Run the same 2-job session through ``repro.launch.cluster`` twice —
  once with ``--spatial`` (2 disjoint single-device submeshes, placement
  rounds genuinely overlap) and once on the shared 2-device host mesh
  (machines are exclusivity slots; steps serialize).  Subprocesses force
  ``xla_force_host_platform_device_count=2``; each mode runs once cold
  to populate a persistent compilation cache, then ``reps`` warm runs,
  and the median warm aggregate steps/s is scored — compile time is
  amortized out of both modes identically.

  On a host where the two virtual devices share one physical core the
  concurrent steps interleave rather than truly parallelize, so the
  spatial margin is only the overlapped host/dispatch overhead; with
  one core per submesh the same harness measures near-2x.

  PYTHONPATH=src python benchmarks/bench_spatial.py [--full]

Every number here is a CPU timing, never a chip measurement: the script
exits non-zero when JAX picks any backend but the CPU, and its children
inherit the parent's platform.
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import subprocess
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
OUT = ROOT / "BENCH_spatial.json"

DEPTHS = (2, 4)                 # the k=2 cycle's step shapes


def _fresh_engines(n: int, *, shared: bool):
    from repro.config import SPBConfig, TrainConfig
    from repro.configs import reduced_config
    from repro.engine import SPBEngine

    cfg = reduced_config("yi-6b")
    return [SPBEngine(cfg, TrainConfig(seed=i, num_steps=64),
                      SPBConfig(mode="temporal", k=2), shared_cache=shared)
            for i in range(n)]


def bench_warmup(counts, *, shared: bool) -> dict:
    """Seconds until N tenants have each executed every cycle depth."""
    import jax

    from repro.configs import reduced_config
    from repro.data.pipeline import Pipeline
    from repro.engine import stepcache

    pipe = Pipeline(reduced_config("yi-6b"), 4, 32, seed=0)
    batch = pipe.get_batch(0)
    points = {}
    for n in counts:
        stepcache.GLOBAL.clear()
        engines = _fresh_engines(n, shared=shared)
        for i, e in enumerate(engines):
            e.init_state(jax.random.key(i))
        t0 = time.perf_counter()
        for step, depth in enumerate(DEPTHS):
            for e in engines:
                jax.block_until_ready(
                    e.train_step(batch, step, depth=depth)["loss"])
        points[n] = {
            "warmup_s": round(time.perf_counter() - t0, 3),
            "stepcache": stepcache.GLOBAL.stats(),
        }
    return points


def _cluster_cmd(iters: int, json_out: str, spatial: bool):
    cmd = [sys.executable, "-m", "repro.launch.cluster",
           "--jobs", "2", "--machines", "2", "--workers", "1",
           "--iters", str(iters), "--arrival", "0.0", "--quiet",
           "--json-out", json_out]
    if spatial:
        cmd.append("--spatial")
    return cmd


def bench_modes(iters: int, reps: int = 2) -> dict:
    """Median warm-run aggregate steps/s: spatial vs time-multiplex."""
    env = {**os.environ,
           "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
           "PYTHONPATH": str(ROOT / "src")}
    modes = {}
    with tempfile.TemporaryDirectory() as td:
        for mode, spatial in (("spatial", True), ("timemux", False)):
            # each mode's runs share one persistent compilation cache
            cc_env = {**env, "JAX_COMPILATION_CACHE_DIR":
                      str(Path(td) / f"cc_{mode}")}
            recs = []
            for run in ["cold"] + [f"warm{i}" for i in range(reps)]:
                jpath = str(Path(td) / f"{mode}_{run}.json")
                subprocess.run(
                    _cluster_cmd(iters, jpath, spatial), env=cc_env,
                    check=True, capture_output=True, timeout=900)
                if run != "cold":       # cold run only primes the cc cache
                    recs.append(json.loads(Path(jpath).read_text()))
            scored = []
            for rec in recs:
                steps = sum(s["steps_run"] for s in rec["summary"].values())
                scored.append((steps / rec["wall_s"], steps, rec))
            scored.sort(key=lambda t: t[0])
            agg, steps, rec = scored[len(scored) // 2]      # median rep
            modes[mode] = {
                "wall_s": round(rec["wall_s"], 3),
                "steps": steps,
                "agg_steps_per_s": round(agg, 3),
                "agg_steps_per_s_reps": [round(a, 3) for a, _, _ in scored],
                "makespan": round(rec["makespan"], 3),
                "max_concurrent_tasks": rec.get("max_concurrent_tasks"),
                "stepcache": rec["stepcache"],
            }
    return modes


def bench(counts=(1, 2, 4), iters: int = 600, reps: int = 2) -> dict:
    per_job = bench_warmup(counts, shared=False)   # pessimistic order:
    shared = bench_warmup(counts, shared=True)     # shared runs second
    n_lo, n_hi = min(counts), max(counts)
    scale_per_job = per_job[n_hi]["warmup_s"] / per_job[n_lo]["warmup_s"]
    scale_shared = shared[n_hi]["warmup_s"] / shared[n_lo]["warmup_s"]
    modes = bench_modes(iters, reps=reps)
    return {
        "platform": platform.platform(),
        "depths": list(DEPTHS),
        "iters": iters,
        "warmup": {"per_job": per_job, "shared": shared},
        # headline 1: shared-cache warmup grows far slower than per-job
        "warmup_scale_per_job": round(scale_per_job, 2),
        "warmup_scale_shared": round(scale_shared, 2),
        "warmup_flat_with_shared_cache": scale_shared < scale_per_job,
        "modes": modes,
        # headline 2: disjoint submeshes beat time-multiplexing
        "spatial_speedup": round(
            modes["spatial"]["agg_steps_per_s"]
            / modes["timemux"]["agg_steps_per_s"], 3),
        "spatial_beats_timemux": (modes["spatial"]["agg_steps_per_s"]
                                  > modes["timemux"]["agg_steps_per_s"]),
    }


def write_json(rec: dict, path: Path = OUT) -> Path:
    path.write_text(json.dumps(rec, indent=2) + "\n")
    return path


def run(quick: bool = True):
    rec = bench(counts=(1, 2) if quick else (1, 2, 4),
                iters=600, reps=2 if quick else 3)
    rec["quick"] = quick
    write_json(rec)
    rows = []
    for kind in ("per_job", "shared"):
        for n, p in rec["warmup"][kind].items():
            sc = p["stepcache"]
            rows.append((
                f"spatial/warmup/{kind}/n{n}", p["warmup_s"] * 1e6,
                f"hits={sc['hits']} misses={sc['misses']} "
                f"entries={sc['entries']}"))
    for mode, m in rec["modes"].items():
        rows.append((
            f"spatial/session/{mode}", m["wall_s"] * 1e6,
            f"steps={m['steps']} agg={m['agg_steps_per_s']:.2f}/s "
            f"max_conc={m['max_concurrent_tasks']}"))
    rows.append(("spatial/speedup", 0.0,
                 f"spatial_vs_timemux={rec['spatial_speedup']:.2f}x "
                 f"warmup_scale shared={rec['warmup_scale_shared']:.2f} "
                 f"per_job={rec['warmup_scale_per_job']:.2f}"))
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()
    import jax
    if jax.default_backend() != "cpu":
        sys.exit(f"bench_spatial.py: a CPU count tool, but JAX chose the "
                 f"{jax.default_backend()!r} backend; run it with "
                 f"JAX_PLATFORMS=cpu")
    for name, us, derived in run(quick=not args.full):
        print(f"{name},{us:.1f},{derived}")
