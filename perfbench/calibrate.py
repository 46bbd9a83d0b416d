"""Readings for setting a cell's correctness limits, in one process.

    python3 perfbench/calibrate.py --workload <name> --seeds 1,2,3 \
        [--control-seeds 1,2,3] [--out <file.jsonl>]

For each seed of ``--seeds``: the program's first steps (set up exactly
as a benchmark run does, compiled once for all seeds) against the float32
reference; for each seed of ``--control-seeds`` also the control (the
reference in the program's place with every matmul operand rounded to
float8 e4m3) and the planted fault of half the batch left out.  Prints one
JSON line per seed and reading, with the numbers ``harness.compare``
gives.  The benchmark's own runs never run the control or the fault.
Needs the cell's chips, as ``run.py`` does.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

CONTROL = {"control_fp8": {"low": "float8_e4m3fn"},
           "fault_half_batch": {"half_batch": True}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    from perfbench import harness, reference, registry, weights
    cell = registry.cell(args.workload, ROOT)
    devices = harness.tpu_devices(cell.chips)
    harness.import_program(ROOT)
    harness.enable_cache()
    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    names = weights.leaf_names(cell.hf)
    out = open(args.out, "a") if args.out else None
    sess = None
    for seed in seeds:
        t = time.perf_counter()
        if sess is None:
            sess = harness.Session(cell, seed, devices)
        else:
            sess.reset(seed)
        prog = harness.first_steps(sess)
        sess.engine.state = None
        readings = {"program": prog}
        if seed in controls:
            for label, kw in CONTROL.items():
                readings[label] = harness.reference_readings(
                    cell.hf, cell.job, seed, prog["batches"], prog["depths"],
                    reference.Variant(**kw), devices)
        ref = harness.reference_readings(cell.hf, cell.job, seed,
                                         prog["batches"], prog["depths"],
                                         devices=devices)
        for label, r in readings.items():
            nums = harness.compare(r, ref, names)
            line = {"workload": cell.name, "seed": seed, "reading": label,
                    "device": devices[0].device_kind,
                    "numbers": {k: v for k, v in nums.items()},
                    "loss": r["loss"], "ref_loss": ref["loss"],
                    "seconds": time.perf_counter() - t}
            print(json.dumps(line), flush=True)
            if out:
                out.write(json.dumps(line) + "\n")
                out.flush()
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
