"""Drives a whole benchmark run at a tiny size on the CPU (the look for a
chip skipped), for the tests that break the timed path underneath.

A fault is planted by name (:data:`FAULTS`) through a ``patch(obj, name,
value)`` such as pytest's ``monkeypatch.setattr``.  A cell on one chip
runs in the test's process; a cell on several runs in a child process
with as many host CPU devices, which plants the fault itself:

    python3 -m perfbench.tests._faults <root> <workload> <fault|-> <seed>

prints the result line as its last line.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

from perfbench import harness, reference, registry
from perfbench.tests import tiny_cells

CELLS = [w["name"] for w in registry.benchmark()["workloads"]]
MULTI = [w["name"] for w in registry.benchmark()["workloads"]
         if w["chips"] > 1]
SEED = 2**31 + 99


def control(patch) -> None:
    """The reference put in the program's place, every matmul operand
    rounded to float8 e4m3 (the precision below bfloat16)."""
    program = harness.first_steps

    def first_steps(sess):
        out = program(sess)
        low = harness.reference_readings(
            sess.hf, sess.job, sess.seed, out["batches"], out["depths"],
            reference.Variant(low="float8_e4m3fn"),
            list(sess.engine.mesh.devices.flat))
        out.update(loss=low["loss"], grad=low["grad"], change=low["change"])
        return out

    patch(harness, "first_steps", first_steps)


def half_batch(patch) -> None:
    """A step that leaves out half of the batch, the mean taken over the
    rest: its second half of rows replaced by its first."""
    import jax.numpy as jnp
    from repro.engine.engine import SPBEngine
    whole = SPBEngine.train_step

    def train_step(self, batch, step=None, **kw):
        n = batch["tokens"].shape[0] // 2
        half = {k: jnp.concatenate([v[:n], v[:n]]) for k, v in batch.items()}
        return whole(self, half, step, **kw)

    patch(SPBEngine, "train_step", train_step)


def state_unchanged(patch) -> None:
    """A step that returns its state unchanged."""
    import jax.numpy as jnp
    from repro.optim import optimizers

    def unchanged(params, grads, opt_state, step, tcfg, **kw):
        return params, opt_state, {"grad_norm": jnp.zeros(()),
                                   "lr": jnp.zeros(())}

    patch(optimizers, "apply_updates", unchanged)


def stage_exchange(patch) -> None:
    """The pipeline's sends between stages left out: each stage receives
    what it sent itself."""
    from repro.dist.pipeline import runtime

    class Lax:
        def __getattr__(self, name):
            return getattr(runtime.jax.lax, name)

        @staticmethod
        def ppermute(x, axis_name, perm):
            return x

    patch(runtime, "lax", Lax())


def tensor_exchange(patch) -> None:
    """The tensor-parallel all-reduce at the joins of a stage left out:
    each chip keeps its partial sum."""
    from repro.models import layers
    patch(layers, "tp_psum", lambda x, axis: x)


FAULTS = {"control": control, "half_batch": half_batch,
          "state_unchanged": state_unchanged,
          "stage_exchange": stage_exchange,
          "tensor_exchange": tensor_exchange}


def _run(root, workload: str, seed: int) -> dict:
    import jax
    cell = registry.cell(workload, root)
    return harness.run(cell, seed, 0.3, False, jax.devices()[:cell.chips],
                       time.perf_counter(), root)


def run_tiny(tmp_path, workload: str, fault=None, monkeypatch=None,
             seed: int = SEED) -> dict:
    """The result of a tiny run of ``workload`` with ``fault`` planted."""
    harness.import_program(registry.ROOT)
    root = tiny_cells.make_root(tmp_path, workload)
    chips = registry.cell(workload, root).chips
    if chips == 1:
        if fault:
            FAULTS[fault](monkeypatch.setattr)
        return _run(root, workload, seed)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={chips}",
               PYTHONPATH=os.pathsep.join([str(registry.ROOT),
                                           str(registry.ROOT / "src")]))
    proc = subprocess.run(
        [sys.executable, "-m", "perfbench.tests._faults", str(root),
         workload, fault or "-", str(seed)],
        cwd=registry.ROOT, env=env, capture_output=True, text=True,
        timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def main(argv) -> int:
    root, workload, fault, seed = argv
    harness.import_program(registry.ROOT)
    if fault != "-":
        FAULTS[fault](setattr)
    print(json.dumps(_run(root, workload, int(seed))), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
