"""Drives a whole benchmark run at a tiny size on the CPU (the look for a
chip skipped), for the tests that break the timed path underneath."""
from __future__ import annotations

import time

from perfbench import harness, registry
from perfbench.tests import tiny_cells

CELLS = [w["name"] for w in registry.benchmark()["workloads"]]


def run_tiny(tmp_path, workload: str, seed: int = 2**31 + 99) -> dict:
    import jax
    harness.import_program(registry.ROOT)
    root = tiny_cells.make_root(tmp_path, workload)
    cell = registry.cell(workload, root)
    return harness.run(cell, seed, 0.3, False, jax.devices()[:1],
                       time.perf_counter(), root)
