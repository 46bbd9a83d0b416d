"""Per-depth SPB step benchmark: wall-clock step time + compiled HLO
flops/bytes/collectives for every snapped suffix depth of the temporal
schedule, written to BENCH_spb_step.json so future perf PRs have a
trajectory to compare against.

The steps are the engine's own compiled table (donated in_shardings
signatures — ``alias_bytes`` in each row proves params/opt-state update
in place), so the benchmark measures exactly what the trainer runs.

A second row set covers pipeline parallelism (``--pipeline-stages``,
default 2): GPipe vs 1F1B vs SPB-truncated 1F1B at each snapped depth,
each row carrying the schedule table's tick count, per-tick bubble
fraction, and the runtime's ring-buffer stash watermark (slots + bytes
per device) — the 1F1B-vs-GPipe memory gap in numbers.  The pipeline
rows run in a child process because the stage mesh needs
``--xla_force_host_platform_device_count`` set before jax initializes.

Every number here is a CPU count or a CPU timing, never a chip
measurement: the script exits non-zero when JAX picks any backend but
the CPU, and its children inherit the parent's platform.

A third row set (``--tensor-parallel``, default 2) prices the 3-D
layouts on a ``(stage, model)`` mesh: replicated compute vs
tensor-sharded stages vs tensor + sequence-parallel, per snapped depth,
with measured collective counts/bytes and the roofline's predicted join
traffic side by side.

  PYTHONPATH=src python benchmarks/bench_spb_step.py [--arch yi-6b]
"""
from __future__ import annotations

import os

if os.environ.get("SPB_BENCH_FORCE_DEVICES"):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count="
        + os.environ["SPB_BENCH_FORCE_DEVICES"])

import argparse
import json
import platform
import subprocess
import sys
import time
from pathlib import Path

import jax

from repro.analysis import hlo
from repro.config import SPBConfig, TrainConfig
from repro.configs import make_batch, reduced_config
from repro.engine import SPBEngine, depth_to_bwd_stages

OUT = Path(__file__).resolve().parents[1] / "BENCH_spb_step.json"


def _measure(engine: SPBEngine, b, key, reps: int) -> dict:
    t0 = time.perf_counter()
    compiled = engine.compile_table(engine.batch_specs_like(b),
                                    depths=[key])[key]
    compile_s = time.perf_counter() - t0
    cost = hlo.analyze(compiled.as_text())
    ma = compiled.memory_analysis()
    # donation consumes the input state, so each timed call chains the
    # returned state (layouts match by construction: out_shardings ==
    # in_shardings)
    engine.init_state(jax.random.key(0))
    jax.block_until_ready(engine.train_step(b, 0, depth=key))     # warmup
    t0 = time.perf_counter()
    for r in range(reps):
        metrics = engine.train_step(b, r + 1, depth=key)
        jax.block_until_ready(metrics["loss"])
    step_ms = (time.perf_counter() - t0) / reps * 1e3
    return {
        "depth": key if key is not None else "full",
        "step_ms": round(step_ms, 2),
        "compile_s": round(compile_s, 2),
        "hlo_flops": cost.flops,
        "hlo_bytes": cost.bytes,
        "hlo_collective_bytes": cost.collective_bytes,
        "alias_bytes": int(getattr(ma, "alias_size_in_bytes", 0)),
    }


def bench(arch: str = "yi-6b", batch: int = 8, seq: int = 128, k: int = 4,
          reps: int = 5) -> dict:
    cfg = reduced_config(arch)
    tcfg = TrainConfig(optimizer="adamw", learning_rate=1e-3)
    spb = SPBConfig(mode="temporal", k=k)

    engine = SPBEngine(cfg, tcfg, spb)
    b = make_batch(cfg, batch, seq)
    rows = [_measure(engine, b, key, reps) for key in engine.depth_keys()]
    return {
        "arch": arch, "batch": batch, "seq": seq, "k": k, "reps": reps,
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "platform": platform.platform(),
        "jax_version": jax.__version__,
        "donate": True,
        "rows": rows,
    }


def bench_pipeline(arch: str, batch: int, seq: int, k: int, reps: int,
                   stages: int, microbatches: int) -> dict:
    """Pipeline-mode rows: GPipe vs 1F1B at full depth, plus SPB-truncated
    1F1B at every snapped depth of the k-cycle.  Runs on a ``stage`` mesh
    of ``stages`` simulated host devices."""
    from repro.analysis.roofline import pipeline_stash_bytes
    from repro.dist.pipeline import schedules

    cfg = reduced_config(arch)
    tcfg = TrainConfig(optimizer="adamw", learning_rate=1e-3,
                       microbatches=microbatches)
    spb = SPBConfig(mode="temporal", k=k)
    rows = []
    pipeline_data = 1
    for kind in ("gpipe", "1f1b"):
        engine = SPBEngine(cfg, tcfg, spb, parallelism="pipeline",
                           pipeline_schedule=kind)
        pipeline_data = engine.pipeline_data
        b = make_batch(cfg, batch, seq)
        keys = engine.depth_keys() if kind == "1f1b" else [None]
        for key in keys:
            row = _measure(engine, b, key, reps)
            bwd = depth_to_bwd_stages(cfg, key, stages)
            sched = schedules.build(kind, stages, microbatches,
                                    bwd_stages=bwd)
            plan = schedules.stash_plan(sched)
            row.update({
                "schedule": kind,
                "bwd_stages": bwd,
                "ticks": sched.num_ticks,
                "bubble_fraction": round(
                    schedules.bubble_fraction_of(sched), 4),
                "max_in_flight": schedules.max_in_flight(sched),
                # the runtime's ring-buffer watermark: what 1F1B's
                # bounded stash (vs GPipe's M) costs in bytes per device
                "stash_slots_act": plan.act_slots,
                "stash_slots_cot": plan.cot_slots,
                "stash_bytes": pipeline_stash_bytes(
                    cfg, batch // microbatches, seq, stages, microbatches,
                    data_parallel=engine.pipeline_data, sched=sched),
            })
            rows.append(row)
    return {"stages": stages, "microbatches": microbatches,
            "pipeline_data": pipeline_data, "rows": rows}


def bench_3d(arch: str, batch: int, seq: int, k: int, reps: int,
             stages: int, microbatches: int, tp: int) -> dict:
    """3-D layout rows on a ``(stage, model)`` mesh: replicated compute
    vs tensor-sharded stages vs tensor + sequence-parallel, per snapped
    SPB depth — step time, per-device temp bytes, measured collective
    counts/bytes (``hlo.collectives``) and the roofline's predicted join
    traffic side by side."""
    from repro.analysis.roofline import pipeline_tp_collective_bytes
    from repro.launch.mesh import make_pipeline_mesh

    cfg = reduced_config(arch)
    tcfg = TrainConfig(optimizer="adamw", learning_rate=1e-3,
                       microbatches=microbatches)
    spb = SPBConfig(mode="temporal", k=k)
    mesh = make_pipeline_mesh(stages, model_parallel=tp)
    b = make_batch(cfg, batch, seq)
    layouts = [("replicated", dict(tensor_parallel=1)),
               ("tensor", dict(tensor_parallel=tp)),
               ("tensor+sp", dict(tensor_parallel=tp,
                                  sequence_parallel=True))]
    rows = []
    for name, kw in layouts:
        engine = SPBEngine(cfg, tcfg, spb, mesh=mesh,
                           parallelism="pipeline", **kw)
        for key in engine.depth_keys():
            row = _measure(engine, b, key, reps)
            compiled = engine.compile_table(engine.batch_specs_like(b),
                                            depths=[key])[key]
            cost = hlo.analyze(compiled.as_text(),
                               num_partitions=stages * tp)
            ma = compiled.memory_analysis()
            bwd = depth_to_bwd_stages(cfg, key, stages)
            row.update({
                "layout": name,
                "bwd_stages": bwd,
                "temp_bytes": int(getattr(ma, "temp_size_in_bytes", 0)),
                "collectives": {op: {k2: round(v2, 1)
                                     for k2, v2 in c.items()}
                                for op, c in cost.collectives().items()},
                "roofline_tp_collective_bytes": pipeline_tp_collective_bytes(
                    cfg, batch // microbatches, seq, stages, microbatches,
                    model_parallel=1 if name == "replicated" else tp,
                    bwd_stages=bwd,
                    sequence_parallel=name == "tensor+sp"),
            })
            rows.append(row)
    return {"stages": stages, "model_parallel": tp,
            "microbatches": microbatches, "rows": rows}


def _spawn_pipeline_child(args) -> dict:
    env = dict(os.environ)
    env["SPB_BENCH_FORCE_DEVICES"] = str(args.pipeline_stages)
    cmd = [sys.executable, __file__, "--_pipeline-child",
           "--arch", args.arch, "--batch", str(args.batch),
           "--seq", str(args.seq), "--k", str(args.k),
           "--reps", str(args.reps),
           "--pipeline-stages", str(args.pipeline_stages),
           "--pipeline-microbatches", str(args.pipeline_microbatches)]
    proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                          timeout=3600)
    if proc.returncode != 0:
        raise RuntimeError(f"pipeline bench child failed:\n"
                           f"{proc.stderr[-3000:]}")
    return json.loads(proc.stdout.split("PIPELINE_JSON:")[-1])


def _spawn_3d_child(args) -> dict:
    env = dict(os.environ)
    env["SPB_BENCH_FORCE_DEVICES"] = str(
        args.pipeline_stages * args.tensor_parallel)
    cmd = [sys.executable, __file__, "--_3d-child",
           "--arch", args.arch, "--batch", str(args.batch),
           "--seq", str(args.seq), "--k", str(args.k),
           "--reps", str(args.reps),
           "--pipeline-stages", str(args.pipeline_stages),
           "--pipeline-microbatches", str(args.pipeline_microbatches),
           "--tensor-parallel", str(args.tensor_parallel)]
    proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                          timeout=3600)
    if proc.returncode != 0:
        raise RuntimeError(f"3-D bench child failed:\n"
                           f"{proc.stderr[-3000:]}")
    return json.loads(proc.stdout.split("PIPELINE_JSON:")[-1])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-6b")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--k", type=int, default=4)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--pipeline-stages", type=int, default=2,
                    help="0 disables the pipeline row set")
    ap.add_argument("--pipeline-microbatches", type=int, default=4)
    ap.add_argument("--tensor-parallel", type=int, default=2,
                    help="model-axis size for the 3-D row set; "
                         "0 disables it")
    ap.add_argument("--_pipeline-child", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--_3d-child", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--out", default=str(OUT))
    args = ap.parse_args()
    if jax.default_backend() != "cpu":
        sys.exit(f"bench_spb_step.py: a CPU count tool, but JAX chose the "
                 f"{jax.default_backend()!r} backend; run it with "
                 f"JAX_PLATFORMS=cpu")

    if getattr(args, "_pipeline_child"):
        rec = bench_pipeline(args.arch, args.batch, args.seq, args.k,
                             args.reps, args.pipeline_stages,
                             args.pipeline_microbatches)
        print("PIPELINE_JSON:" + json.dumps(rec))
        return
    if getattr(args, "_3d_child"):
        rec = bench_3d(args.arch, args.batch, args.seq, args.k, args.reps,
                       args.pipeline_stages, args.pipeline_microbatches,
                       args.tensor_parallel)
        print("PIPELINE_JSON:" + json.dumps(rec))
        return

    rec = bench(args.arch, args.batch, args.seq, args.k, args.reps)
    if args.pipeline_stages > 0:
        rec["pipeline"] = _spawn_pipeline_child(args)
        if args.tensor_parallel > 1:
            rec["pipeline_3d"] = _spawn_3d_child(args)
    Path(args.out).write_text(json.dumps(rec, indent=2) + "\n")
    for r in rec["rows"]:
        print(f"depth={r['depth']!s:>4}  step={r['step_ms']:8.2f}ms  "
              f"flops={r['hlo_flops']:.3e}  bytes={r['hlo_bytes']:.3e}  "
              f"alias={r['alias_bytes']:.2e}")
    for r in rec.get("pipeline", {}).get("rows", []):
        print(f"pipe[{r['schedule']:>5}] depth={r['depth']!s:>4} "
              f"bwd_stages={r['bwd_stages']} step={r['step_ms']:8.2f}ms  "
              f"flops={r['hlo_flops']:.3e}  bubble={r['bubble_fraction']} "
              f"ticks={r['ticks']} stash={r['stash_slots_act']}+"
              f"{r['stash_slots_cot']}={r['stash_bytes']/2**10:.0f}KiB")
    for r in rec.get("pipeline_3d", {}).get("rows", []):
        ag = r["collectives"].get("all-gather", {}).get("payload_bytes", 0)
        print(f"3d[{r['layout']:>10}] depth={r['depth']!s:>4} "
              f"step={r['step_ms']:8.2f}ms  temp={r['temp_bytes']:.2e}  "
              f"coll={r['hlo_collective_bytes']:.2e} ag={ag:.2e} "
              f"roofline={r['roofline_tp_collective_bytes']:.2e}")
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
