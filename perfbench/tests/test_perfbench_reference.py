"""The float32 reference gives the same readings with its parameters,
gradient and moments split over four devices as on one (a tiny
stand-in on host CPU devices, in a child process: the device count is
fixed when JAX starts), and the same readings as before the
architectures moved into files of their own."""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from perfbench import registry
from perfbench.tests import tiny_cells

CHILD = """
import json, sys
import jax
import numpy as np
from perfbench import harness, registry
harness.import_program(registry.ROOT)
cell = registry.cell(sys.argv[1], sys.argv[2])
rng = np.random.default_rng(7)
batches = []
for _ in range(3):
    t = rng.integers(0, cell.hf["vocab_size"], (cell.job["batch"],
                     cell.job["seq_len"] + 1), dtype=np.int32)
    batches.append({"tokens": t[:, :-1], "labels": t[:, 1:]})
depths = [8, 4, 8]
out = {}
for n in (1, 4):
    r = harness.reference_readings(cell.hf, cell.job, 2**31 + 5, batches,
                                   depths, devices=jax.devices()[:n])
    out[n] = {k: np.asarray(r[k]).tolist() for k in ("loss", "grad",
                                                     "change")}
print(json.dumps(out))
"""


def test_sharded_reference_agrees_with_one_device(tmp_path):
    workload = "yi6b_8l.pp2tp2.spb_k2"
    root = tiny_cells.make_root(tmp_path, workload)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(registry.ROOT),
                                           str(registry.ROOT / "src")]))
    proc = subprocess.run([sys.executable, "-c", CHILD, workload, str(root)],
                          cwd=registry.ROOT, env=env, capture_output=True,
                          text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    one, four = out["1"], out["4"]
    for k in ("loss", "grad", "change"):
        a, b = np.asarray(one[k]), np.asarray(four[k])
        assert a.shape == b.shape and np.all(np.isfinite(a))
        # float32 rounding: sums taken in another order on four devices
        np.testing.assert_allclose(b, a, rtol=2e-5, atol=0, err_msg=k)


READINGS = json.loads((Path(__file__).resolve().parent / "data"
                       / "readings.json").read_text())["reference"]


@pytest.mark.parametrize("pair", sorted(READINGS))
def test_reference_unchanged(pair):
    """The reference's losses, first gradient and change at each
    configuration's CPU stand-in widths, with its weights in the recorded
    dtype, equal, bit for bit, those recorded before the architectures
    moved into files of their own."""
    from perfbench import harness
    harness.import_program(registry.ROOT)
    config, traffic = pair.split("|")
    pb = registry.ROOT / registry.HERE
    want = READINGS[pair]
    hf = registry.canonical(dict(tiny_cells.shrink(json.loads(
        (pb / "configs" / f"{config}.json").read_text())),
        torch_dtype=want["torch_dtype"]))
    job = dict(json.loads((pb / "traffic" / f"{traffic}.json").read_text()),
               seq_len=64)
    rng = np.random.default_rng(7)
    batches = []
    for _ in want["depths"]:
        t = rng.integers(0, hf["vocab_size"],
                         (job["batch"], job["seq_len"] + 1), dtype=np.int32)
        batches.append({"tokens": t[:, :-1], "labels": t[:, 1:]})
    got = harness.reference_readings(hf, job, 2**31 + 5, batches,
                                     want["depths"])
    for k in ("loss", "grad", "change"):
        assert np.asarray(got[k], np.float64).tolist() == want[k], k
