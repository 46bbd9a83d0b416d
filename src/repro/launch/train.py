"""End-to-end training driver: a thin client of ``repro.engine.SPBEngine``
with checkpointing and auto-restart.

Examples (CPU host mesh, reduced configs):
  python -m repro.launch.train --arch yi-6b --reduced --steps 60 \\
      --spb-mode temporal --spb-k 4 --checkpoint-dir /tmp/ckpt
  python -m repro.launch.train --arch yi-6b --reduced --steps 30 \\
      --spb-mode temporal --depth-policy costmodel --time-budget 0.6
  python -m repro.launch.train --arch yi-6b --reduced --steps 20 \\
      --spb-mode temporal --aot-cache results/aot_cache   # reuse compiles

The engine owns mesh/state/step-table; this driver owns the loop: data,
logging, checkpoints, and the supervision loop that catches step failures
(and the ``--fail-at`` injection used by tests), restores the latest
checkpoint and resumes — on a different DP width if the device count
changed (elastic).
"""
from __future__ import annotations

import argparse
import time

import jax

from repro.checkpoint.manager import CheckpointManager
from repro.config import SPBConfig, TrainConfig
from repro.configs import get_config, reduced_config
from repro.data.pipeline import Pipeline
from repro.engine import SPBEngine, make_policy
from repro.launch.mesh import make_host_mesh, make_pipeline_mesh


def build_engine(cfg, tcfg, spb_cfg, mesh, *, depth_policy: str = "cycle",
                 time_budget: float = 0.75, donate: bool = True,
                 parallelism: str = "spmd",
                 pipeline_schedule: str = "1f1b",
                 tensor_parallel=None, sequence_parallel: bool = False,
                 zero2: bool = False) -> SPBEngine:
    """The one construction path every entry point shares."""
    engine = SPBEngine(cfg, tcfg, spb_cfg, mesh=mesh, donate=donate,
                       parallelism=parallelism,
                       pipeline_schedule=pipeline_schedule,
                       tensor_parallel=tensor_parallel,
                       sequence_parallel=sequence_parallel, zero2=zero2)
    # build the policy against engine.spb, which the engine has stamped
    # with the mesh's pipeline stage count (stage-snapped depth cycles)
    engine.policy = make_policy(depth_policy, cfg, engine.spb,
                                time_budget_frac=time_budget)
    return engine


def train(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-6b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--spb-mode", default="off",
                    choices=["off", "temporal", "temporal-mb", "spatial"])
    ap.add_argument("--spb-k", type=int, default=4)
    ap.add_argument("--spb-warmup", type=int, default=0)
    ap.add_argument("--parallelism", default="spmd",
                    choices=["spmd", "pipeline"],
                    help="pipeline: run the layer stack as a schedule-"
                         "driven pipeline over a 'stage' mesh axis")
    ap.add_argument("--pipeline-stages", type=int, default=0,
                    help="pipeline stage count (default: all devices "
                         "divided by the data/model factors)")
    ap.add_argument("--pipeline-schedule", default="1f1b",
                    choices=["1f1b", "gpipe"])
    ap.add_argument("--pipeline-data-parallel", type=int, default=1,
                    help="size of the pipeline mesh's 'data' axis: "
                         "microbatches shard their batch dim over it and "
                         "per-stage optimizer moments ZeRO-1-shard over it "
                         "(total devices = stages x data x model)")
    ap.add_argument("--tensor-parallel", type=int, default=1,
                    help="size of the pipeline mesh's 'model' axis: stage "
                         "weights column/row-shard over it with explicit "
                         "collectives at the attention/MLP joins")
    ap.add_argument("--sequence-parallel", action="store_true",
                    help="with --tensor-parallel > 1: shard the in-stage "
                         "residual stream over 'model' on the sequence dim "
                         "(all-gather/reduce-scatter at the joins)")
    ap.add_argument("--zero2", action="store_true",
                    help="reduce-scatter pipeline stage grads over 'data' "
                         "into the ZeRO-1 moments' layout")
    ap.add_argument("--depth-policy", default="cycle",
                    choices=["cycle", "costmodel", "hook"],
                    help="who picks the per-step backprop depth")
    ap.add_argument("--time-budget", type=float, default=0.75,
                    help="costmodel policy: step-time budget as a fraction "
                         "of a full-backprop step")
    ap.add_argument("--no-donate", action="store_true",
                    help="disable buffer donation (debugging)")
    ap.add_argument("--aot-cache", default="",
                    help="directory of serialized step tables (same cache "
                         "the dry-run writes); a process with matching "
                         "config + mesh topology reuses the table with no "
                         "re-trace/re-compile")
    ap.add_argument("--compression", default="none")
    ap.add_argument("--checkpoint-dir", default="")
    ap.add_argument("--checkpoint-every", type=int, default=20)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--fail-at", type=int, default=-1,
                    help="inject a failure at this step (tests)")
    ap.add_argument("--max-restarts", type=int, default=2)
    ap.add_argument("--use-pallas", action="store_true",
                    help="route SSM scans (SSD / RG-LRU) through the "
                         "Pallas kernels (interpret mode on CPU)")
    args = ap.parse_args(argv)

    cfg = reduced_config(args.arch) if args.reduced else get_config(args.arch)
    if args.use_pallas:
        import dataclasses as _dc
        cfg = _dc.replace(cfg, use_pallas=True)
    tcfg = TrainConfig(learning_rate=args.lr, optimizer=args.optimizer,
                       num_steps=args.steps, microbatches=args.microbatches,
                       compression=args.compression,
                       checkpoint_every=args.checkpoint_every,
                       checkpoint_dir=args.checkpoint_dir, seed=args.seed)
    spb_cfg = SPBConfig(mode=args.spb_mode, k=args.spb_k,
                        warmup_steps=args.spb_warmup)
    if args.parallelism == "pipeline":
        mesh = make_pipeline_mesh(args.pipeline_stages or None,
                                  data_parallel=args.pipeline_data_parallel,
                                  model_parallel=args.tensor_parallel)
    else:
        mesh = make_host_mesh()
    mgr = (CheckpointManager(tcfg.checkpoint_dir, keep=3)
           if tcfg.checkpoint_dir else None)

    restarts = 0
    history = []
    while True:
        try:
            history = _run(cfg, tcfg, spb_cfg, mesh, args, mgr, history)
            break
        except RuntimeError as e:      # noqa: PERF203
            restarts += 1
            print(f"[train] FAILURE: {e}; restart {restarts}", flush=True)
            if restarts > args.max_restarts or mgr is None:
                raise
            args.fail_at = -1          # don't re-inject
            args.resume = True
    if mgr:
        mgr.wait()
    return history


def _run(cfg, tcfg, spb_cfg, mesh, args, mgr, history):
    engine = build_engine(cfg, tcfg, spb_cfg, mesh,
                          depth_policy=args.depth_policy,
                          time_budget=args.time_budget,
                          donate=not args.no_donate,
                          parallelism=args.parallelism,
                          pipeline_schedule=args.pipeline_schedule,
                          tensor_parallel=(args.tensor_parallel
                                           if args.parallelism == "pipeline"
                                           else None),
                          sequence_parallel=args.sequence_parallel,
                          zero2=args.zero2)
    engine.init_state(jax.random.key(tcfg.seed))
    start_step = 0
    if args.resume and mgr and mgr.latest_step() is not None:
        state, start_step = mgr.restore(engine.state)
        engine.attach_state(state)
        print(f"[train] resumed from step {start_step}", flush=True)

    pipe = Pipeline(cfg, args.batch, args.seq, seed=tcfg.seed)
    if args.aot_cache:
        specs = engine.batch_specs_like(pipe.get_batch(0))
        path = engine.aot_cache_path(specs, args.aot_cache)
        if engine.load_aot(path):
            print(f"[train] AOT step table loaded from {path} "
                  f"(no re-trace)", flush=True)
        else:
            engine.compile_table(specs)
            engine.export_aot(path)
            print(f"[train] AOT step table compiled + exported to {path}",
                  flush=True)

    t0 = time.time()
    for step in range(start_step, tcfg.num_steps):
        if step == args.fail_at:
            raise RuntimeError("injected failure")
        metrics = engine.train_step(pipe.get_batch(step), step)
        if step % args.log_every == 0 or step == tcfg.num_steps - 1:
            m = {k: float(v) for k, v in metrics.items()}
            print(f"[train] step={step:5d} depth={engine.last_depth!s:>4} "
                  f"loss={m['loss']:.4f} xent={m['xent']:.4f} "
                  f"gnorm={m['grad_norm']:.3f} lr={m['lr']:.2e} "
                  f"({time.time()-t0:.1f}s)", flush=True)
        history.append(float(metrics["xent"]))
        if mgr and (step + 1) % tcfg.checkpoint_every == 0:
            mgr.save(jax.device_get(engine.state), step + 1)
    return history


if __name__ == "__main__":
    from repro.engine.stepcache import enable_compilation_cache
    enable_compilation_cache()
    train()
