"""Run one cell of the chip benchmark and print its result line.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

From the root of a checkout on a host with the cell's chips.  The last
line of standard output is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``; its last key, ``checks``, holds each number compared with
its limit).  The last lines of standard error repeat the checks.  Exits
non-zero, with no result line, when JAX finds no TPU or fewer chips than
the cell asks for, or when anything of the cell or the program is missing.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")

    # before JAX is imported: its persistent compilation cache lives at a
    # fixed directory inside the checkout
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    from perfbench import harness, registry
    try:
        cell = registry.cell(args.workload, ROOT)
    except (KeyError, FileNotFoundError) as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 2
    try:
        devices = harness.tpu_devices(cell.chips)
    except harness.NoChip as e:
        print(f"perfbench: {e}; nothing ran", file=sys.stderr)
        return 3
    try:
        harness.import_program(ROOT)
        harness.enable_cache()
        result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                             devices, T_START, ROOT)
    except Exception:  # noqa: BLE001 - the run's boundary: report, exit 1
        traceback.print_exc()
        return 1
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']:.6g} limit {c['limit']:.6g} "
              f"(worst at {c['at']})", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
