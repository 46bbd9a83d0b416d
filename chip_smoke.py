"""Smoke run of the SPB trainer on TPU, through the entry points a user calls.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # 2 pipeline stages x 2-way tensor
                                        # parallelism on one 2x2 host

One chip: yi-6b at its published widths with the depth cut from 32 layers
to 2 (every other width and the 64,000-id vocabulary as published, random
weights from ``--seed``) goes through ``launch.train.build_engine`` ->
``SPBEngine`` with temporal SPB (k=2), so the policy alternates full
backprop (depth 2) with a truncated suffix (depth 1).  Batches come from
``data.pipeline.Pipeline``.  The session runs twice on the same seed and
batches: once on the jnp attention path, once on the Pallas kernels, whose
compiled steps must hold Mosaic kernels (``tpu_custom_call``).

Four chips (``--four-chips``, and only that phase): the same model as a
1F1B pipeline over ``make_pipeline_mesh(2, model_parallel=2)`` with 4
microbatches, at full and truncated depth, compared against the plain
``spmd`` step on a one-device mesh of ``jax.devices()[0]``.

Checks, each of which ends the run with a non-zero exit: every loss and
grad norm is finite; the two paths' first-step losses agree within
:data:`LOSS_RTOL`; the Pallas steps hold Mosaic kernels; no device is over
its memory limit.  Step times printed here are smoke timings, not
measurements.  Without a TPU the script exits non-zero before it runs
anything; its last line is JSON only when every check passed.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import sys
import time
from pathlib import Path

import jax
import numpy as np

#: Relative tolerance between the first-step losses of two paths of the same
#: model on the same batch.  Both run bf16 activations and differ only in the
#: order of their roundings (attention kernel vs blockwise jnp; pipelined and
#: tensor-sharded vs one device), so they agree to about one bf16 epsilon
#: (2**-8 = 3.9e-3) of the loss.
LOSS_RTOL = 5e-3
#: Largest ratio of bytes in use between two devices of the four-chip mesh:
#: each holds one stage's half of the layer weights and half the vocabulary,
#: so a ratio near 2 means state piled up on one device.
MAX_IMBALANCE = 1.5
SEQ = 2048
ARCH = "yi-6b"
LAYERS = 2


def require(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def tpu_devices():
    """The TPU devices, or exit at once: this script has no CPU path."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, but JAX found "
                         f"{devices[0].platform!r} devices; nothing ran")
    return devices


def one_device_mesh(device):
    return jax.sharding.Mesh(np.asarray([device]).reshape(1, 1),
                             ("data", "model"))


def train_phase(cfg, tcfg, mesh, *, batch: int, seq: int, seed: int,
                steps_per_depth: int = 3, parallelism: str = "spmd",
                label: str = "") -> dict:
    """Build a temporal-SPB session, AOT-compile the depths its policy
    picks, then run ``steps_per_depth`` steps at each depth.

    Returns per-step (depth, loss, grad norm, seconds), compile seconds,
    each compiled depth's Mosaic kernel count and each mesh device's
    memory stats while the session's state is live; then frees that state.
    """
    from repro.config import SPBConfig
    from repro.data.pipeline import Pipeline
    from repro.launch.train import build_engine

    engine = build_engine(cfg, tcfg, SPBConfig(mode="temporal", k=2), mesh,
                          parallelism=parallelism)
    engine.init_state(jax.random.key(seed))
    pipe = Pipeline(cfg, batch, seq, seed=seed)
    depths = {}
    for step in range(2 * steps_per_depth):
        depths.setdefault(engine.depth_key_for_step(step), []).append(step)
    require(len(depths) == 2 and
            all(len(s) == steps_per_depth for s in depths.values()),
            f"{label}: the SPB policy ran depths {depths}, expected two "
            f"depths with {steps_per_depth} steps each")

    t0 = time.perf_counter()
    compiled = engine.compile_table(
        engine.batch_specs_like(pipe.get_batch(0)), depths=list(depths))
    compile_s = time.perf_counter() - t0
    kernels = {d: c.as_text().count("tpu_custom_call")
               for d, c in compiled.items()}

    steps = []
    for step in range(2 * steps_per_depth):
        b = pipe.get_batch(step)
        t0 = time.perf_counter()
        metrics = jax.block_until_ready(engine.train_step(b, step))
        secs = time.perf_counter() - t0
        steps.append({"step": step, "depth": engine.last_depth,
                      "loss": float(metrics["loss"]),
                      "grad_norm": float(metrics["grad_norm"]),
                      "seconds": secs})
    for s in steps:
        require(math.isfinite(s["loss"]) and math.isfinite(s["grad_norm"]),
                f"{label}: step {s['step']} at depth {s['depth']}: loss "
                f"{s['loss']}, grad norm {s['grad_norm']}")
    memory = {d.id: d.memory_stats() or {} for d in mesh.devices.flat}
    engine.state = None
    del engine
    gc.collect()
    return {"steps": steps, "compile_s": compile_s, "kernels": kernels,
            "memory": memory}


def print_phase(label: str, res: dict) -> None:
    print(f"[{label}] compile {res['compile_s']:.1f} s for depths "
          f"{sorted(res['kernels'])}; Mosaic kernels per compiled step "
          f"{res['kernels']}", flush=True)
    for s in res["steps"]:
        print(f"[{label}] step {s['step']} depth {s['depth']} loss "
              f"{s['loss']:.6f} grad_norm {s['grad_norm']:.6f} "
              f"step wall {s['seconds'] * 1e3:.1f} ms (smoke timing, not a "
              f"metric)", flush=True)
    for dev, stats in res["memory"].items():
        print(f"[{label}] device {dev} with the state live: bytes_in_use "
              f"{stats.get('bytes_in_use')} peak_bytes_in_use "
              f"{stats.get('peak_bytes_in_use')} bytes_limit "
              f"{stats.get('bytes_limit')}", flush=True)


def require_close(label: str, ref: dict, got: dict) -> None:
    a, b = ref["steps"][0]["loss"], got["steps"][0]["loss"]
    rel = abs(a - b) / abs(a)
    print(f"[{label}] first-step loss {b:.6f} vs {a:.6f}: relative "
          f"difference {rel:.2e} (tolerance {LOSS_RTOL:.0e})", flush=True)
    require(rel <= LOSS_RTOL, f"{label}: first-step losses {a} and {b} "
            f"differ by {rel:.2e} > {LOSS_RTOL}")


def require_memory(label: str, res: dict) -> None:
    """Every device reports its peak, under its limit."""
    for dev, stats in res["memory"].items():
        peak, limit = stats.get("peak_bytes_in_use"), stats.get("bytes_limit")
        require(peak is not None and limit is not None and peak <= limit,
                f"{label}: device {dev}: peak bytes {peak}, limit {limit}")


def one_chip(cfg, tcfg, device, seed: int, batch: int) -> None:
    mesh = one_device_mesh(device)
    res = {}
    for use_pallas in (False, True):
        label = "pallas" if use_pallas else "jnp"
        res[label] = train_phase(
            dataclasses.replace(cfg, use_pallas=use_pallas), tcfg, mesh,
            batch=batch, seq=SEQ, seed=seed, label=label)
        print_phase(label, res[label])
        require_memory(label, res[label])
    require(all(res["pallas"]["kernels"].values()),
            f"the Pallas steps hold no Mosaic kernel: "
            f"{res['pallas']['kernels']}")
    require_close("pallas vs jnp", res["jnp"], res["pallas"])


def four_chips(cfg, tcfg, devices, seed: int, batch: int) -> None:
    from repro.launch.mesh import make_pipeline_mesh
    require(len(devices) >= 4, f"--four-chips needs 4 devices, JAX found "
            f"{len(devices)}")
    ref = train_phase(cfg, tcfg, one_device_mesh(devices[0]), batch=batch,
                      seq=SEQ, seed=seed, steps_per_depth=1,
                      label="spmd 1 device")
    print_phase("spmd 1 device", ref)
    pipe = train_phase(cfg, tcfg, make_pipeline_mesh(2, model_parallel=2),
                       batch=batch, seq=SEQ, seed=seed,
                       parallelism="pipeline", label="pipeline 2x2")
    print_phase("pipeline 2x2", pipe)
    require(all(pipe["kernels"].values()),
            f"the pipeline steps hold no Mosaic kernel: {pipe['kernels']}")
    require_close("pipeline 2x2 vs spmd 1 device", ref, pipe)
    require_memory("pipeline 2x2", pipe)
    in_use = [s["bytes_in_use"] for s in pipe["memory"].values()]
    print(f"[pipeline 2x2] bytes_in_use max / min over the 4 devices: "
          f"{max(in_use) / min(in_use):.3f}", flush=True)
    require(max(in_use) <= MAX_IMBALANCE * min(in_use),
            f"pipeline 2x2: bytes in use per device {in_use} are more "
            f"than {MAX_IMBALANCE}x apart")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2-stage x 2-way tensor-parallel "
                         "pipeline phase and its one-device reference")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = tpu_devices()
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.config import TrainConfig
    from repro.configs import get_config
    from repro.engine.stepcache import enable_compilation_cache
    cache = enable_compilation_cache()
    print(f"[smoke] compilation cache {cache}", flush=True)

    published = get_config(ARCH)
    cfg = published.scaled(num_layers=LAYERS, use_pallas=True)
    print(f"[smoke] {ARCH}: depth {published.num_layers} -> {LAYERS} "
          f"layers; d_model {cfg.d_model}, {cfg.num_heads} q / "
          f"{cfg.num_kv_heads} kv heads x {cfg.head_dim}, d_ff {cfg.d_ff}, "
          f"vocab {cfg.vocab_size} as published; random weights, seed "
          f"{args.seed}", flush=True)
    if args.four_chips:
        tcfg = TrainConfig(microbatches=4, seed=args.seed)
        print(f"[smoke] four chips: batch 4 x {SEQ}, 4 microbatches",
              flush=True)
        four_chips(cfg, tcfg, devices, args.seed, batch=4)
    else:
        tcfg = TrainConfig(seed=args.seed)
        print(f"[smoke] one chip: batch 2 x {SEQ}", flush=True)
        one_chip(cfg, tcfg, devices[0], args.seed, batch=2)
    d = devices[0]
    print(json.dumps({"ok": True, "device": {"platform": d.platform,
                                             "kind": d.device_kind,
                                             "count": len(devices)}}))


if __name__ == "__main__":
    main()
