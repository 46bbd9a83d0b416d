"""Every cell of BENCHMARK.json resolves to its files, and a configuration,
a traffic mix, a cell, a per-layer metric, an architecture and a kernel
family added as new files are found without editing any file that is
there."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from perfbench import harness, registry

BENCH = registry.benchmark()
NAMES = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("name", NAMES)
def test_cell_resolves(name):
    cell = registry.cell(name)
    assert cell.hf["num_hidden_layers"] >= 1 and cell.job["batch"] >= 1
    assert set(cell.limits["limits"]) == set(harness.NUMBERS)
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in cell.per_layer:
        assert callable(registry.reader(m["name"]))


def test_contract_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    configs = {c["name"] for c in BENCH["configs"]}
    assert configs == {w["config"] for w in BENCH["workloads"]}
    moves = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in moves
        assert set(m.get("workloads", NAMES)) <= set(NAMES)
    for c in BENCH["configs"]:
        hf = json.loads((registry.ROOT / c["file"]).read_text())
        assert hf["reduced"] == c["reduced"]


def test_new_files_are_found(tmp_path):
    root = tmp_path
    shutil.copytree(registry.ROOT / "perfbench", root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads(json.dumps(BENCH))
    old = bench["configs"][0]
    hf = json.loads((registry.ROOT / old["file"]).read_text())
    hf["name"] = "added_cfg"
    (root / "perfbench/configs/added_cfg.json").write_text(json.dumps(hf))
    job = registry.cell(NAMES[0]).job
    (root / "perfbench/traffic/added_mix.json").write_text(
        json.dumps(dict(job, batch=1)))
    (root / "perfbench/limits/added_cfg.added_mix.json").write_text(
        json.dumps({"limits": {"loss_gap": 1, "grad_gap": 1,
                               "update_gap": 1}}))
    (root / "perfbench/metrics/added_metric.py").write_text(
        "def read(rec):\n    return 42.0\n")
    bench["configs"].append(dict(old, name="added_cfg",
                                 file="perfbench/configs/added_cfg.json"))
    bench["workloads"].append({"name": "added_cfg.added_mix",
                               "config": "added_cfg", "traffic": "added_mix",
                               "chips": 1, "why": "added"})
    bench["per_layer"].append({"name": "added_metric", "unit": "ms",
                               "better": "lower", "source": "host_clock",
                               "layer": "engine", "moves": "tokens_per_s",
                               "workloads": ["added_cfg.added_mix"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = registry.cell("added_cfg.added_mix", root)
    assert cell.hf["name"] == "added_cfg" and cell.job["batch"] == 1
    assert [m["name"] for m in cell.per_layer] == ["added_metric"]
    assert registry.reader("added_metric", root)({}) == 42.0
    with pytest.raises(KeyError):
        registry.cell("no_such_cell", root)


TOY_ARCH = '''
"""A made-up architecture: pre-norm SwiGLU MLP layers and no mixer."""
import jax

from perfbench import counts, reference
from perfbench.reference import F32, ein, rms

ALIASES = {"n_embd": "hidden_size"}
STACKS = []


def init_gain(key, shape):
    return jax.random.normal(key, shape, F32) * 0.01


def layer_spec(hf):
    d, f = hf["hidden_size"], hf["intermediate_size"]
    return {"ln": ((d,), "bfloat16", init_gain),
            "ffn.wg": ((d, f), "bfloat16", "fan_in"),
            "ffn.wu": ((d, f), "bfloat16", "fan_in"),
            "ffn.wd": ((f, d), "bfloat16", "fan_in")}


def layer(p, x, hf, low):
    m = rms(x, p["ln"], hf["rms_norm_eps"])
    gate = ein("bsd,df->bsf", m, p["ffn"]["wg"], low=low)
    up = ein("bsd,df->bsf", m, p["ffn"]["wu"], low=low)
    return x + ein("bsf,fd->bsd", jax.nn.silu(gate) * up, p["ffn"]["wd"],
                   low=low)


def stack(params, x, hf, low, boundary):
    STACKS.append(boundary)
    return reference.scan_layers(layer, params, x, hf, low, boundary)


def layer_flops(hf, job):
    b, s, d = counts.dims(hf, job)
    mm = 2 * b * s * d * hf["intermediate_size"]
    return {"fwd_mm": 3 * mm, "fwd_mixer": 0, "entry_mm": 2 * mm}


def program_keys(hf):
    return {"d_ff": hf["intermediate_size"]}


STAND_IN = ({"n_embd": 64, "intermediate_size": 128, "vocab_size": 512},
            {"d_model": 64, "num_heads": 4, "num_kv_heads": 2,
             "head_dim": 16, "d_ff": 128, "vocab_size": 512})
'''

TOY_KERNELS = '''
"""A made-up kernel family."""
from perfbench import counts

WRAPPER = "_toy_jit"
KINDS = ("toy_fwd", "toy_bwd")


def kind(results, operands):
    return {1: "toy_fwd", 2: "toy_bwd"}.get(len(results))


def cost(hf, job, kind):
    rows = counts.call_shape(job)["rows"] * job["seq_len"]
    return {"flops": 2 * rows * hf["hidden_size"] * KINDS.index(kind) + 1,
            "bytes": 4 * rows * hf["hidden_size"]}
'''

TOY_CONFIG = {"name": "toy_2l", "model_type": "toy_mlp", "n_embd": 4096,
              "intermediate_size": 11008, "num_hidden_layers": 2,
              "vocab_size": 64000, "rms_norm_eps": 1e-05,
              "tie_word_embeddings": True, "torch_dtype": "bfloat16",
              "reduced": [],
              "trainer": {"arch": "yi-6b",
                          "overrides": {"num_layers": 2, "norm_eps": 1e-05}}}

#: Run from the scratch checkout's root, so that ``perfbench`` is its copy.
TOY_CHILD = '''
import json, zlib
from pathlib import Path
import jax, jax.numpy as jnp, numpy as np
import pytest
from perfbench import (counts, harness, readers, reference, registry,
                       tracefile, weights)
from perfbench.tests import tiny_cells
assert registry.ROOT == Path.cwd().resolve(), registry.ROOT
harness.import_program(registry.ROOT)
toy = registry.arch("toy_mlp")
assert Path(toy.__file__).parent == registry.ROOT / "perfbench/archs"
raw = json.loads(Path("perfbench/configs/toy_2l.json").read_text())
# the stand-in: the architecture's widths and trainer overrides
hf = registry.canonical(tiny_cells.shrink(raw))
assert hf["hidden_size"] == 64 and hf["intermediate_size"] == 128
assert hf["trainer"]["overrides"]["d_ff"] == 128
job = {"batch": 2, "seq_len": 16}
# weights: the layout, with the module's own init rule
key = weights.seed_key(2**31 + 3)
p = weights.init_params(hf, key)
ln = p["groups"][0][0]["ln"]
assert ln.shape == (2, 64) and ln.dtype == jnp.bfloat16
want = (jax.random.normal(jax.random.fold_in(key, zlib.crc32(b"layers.ln")),
                          (2, 64), jnp.float32) * 0.01).astype(jnp.bfloat16)
assert np.array_equal(np.asarray(ln), np.asarray(want))
assert p["groups"][0][0]["ffn"]["wd"].shape == (2, 128, 64)
assert "layers.1.ffn.wg" in weights.leaf_names(hf)
# the reference, through the module's own stack
rng = np.random.default_rng(0)
t = rng.integers(0, 512, (2, 17), dtype=np.int32)
loss = reference.loss(weights.master_params(hf, key), t[:, :-1], t[:, 1:],
                      hf, 1, reference.Variant())
assert np.isfinite(float(loss)) and toy.STACKS == [1]
# counts
mm = 2 * 2 * 16 * 64 * 128
head = 2 * 2 * 16 * 64 * 512
assert counts.step_flops(hf, job, None) == 2 * 3 * mm + 3 * head + 4 * 3 * mm
assert counts.step_flops(hf, job, 1) == \\
    2 * 3 * mm + 3 * head + 2 * 3 * mm - 2 * mm
# the key check
cfg = harness.program_config(hf)
assert cfg.d_ff == 128
with pytest.raises(ValueError, match="d_ff"):
    harness.program_config(dict(hf, intermediate_size=256))
# the trace's kinds and the family's costs
op = ('%_toy_jit.2 = (f32[8]{0}, f32[8]{0}) custom-call(f32[8]{0} %a), '
      'custom_call_target="tpu_custom_call"')
assert tracefile.kernel_kind(op) == "toy_bwd"
assert tracefile.kernel_kind(op.replace("(f32[8]{0}, f32[8]{0})",
                                        "f32[8]{0}")) == "toy_fwd"
assert tracefile.kernel_kind(op.replace("_toy_jit", "_other_jit")) is None
assert counts.kernel_cost(hf, job, "toy_bwd") == {
    "flops": 2 * 32 * 64 + 1, "bytes": 4 * 32 * 64}
rec = {"hf": hf, "job": job, "peaks": {"flops": 1e6, "hbm_bytes_per_s": 1e9},
       "trace": {"kernels": {"toy_bwd": {"calls": 3, "seconds": 0.5}}}}
assert readers.roofline_share(rec, "toy_") == \\
    100.0 * 3 * max(4097 / 1e6, 8192 / 1e9) / 0.5
print("ok")
'''


def test_architecture_and_kernel_family_added_as_files(tmp_path):
    """A made-up architecture and a made-up kernel family, each one new
    file in a scratch checkout, reach the weights, the reference (with a
    stack of its own), the counts, the key check against the trainer, the
    CPU stand-in and the trace's kinds, with no other file edited."""
    root = tmp_path
    shutil.copytree(registry.ROOT / "perfbench", root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    (root / "perfbench/archs/toy_mlp.py").write_text(TOY_ARCH)
    (root / "perfbench/kernels/toy.py").write_text(TOY_KERNELS)
    (root / "perfbench/configs/toy_2l.json").write_text(
        json.dumps(TOY_CONFIG))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(registry.ROOT / "src"))
    proc = subprocess.run([sys.executable, "-c", TOY_CHILD], cwd=root,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert proc.stdout.strip().splitlines()[-1] == "ok"


def test_unknown_architecture_or_kind_names_the_file():
    from perfbench import counts, weights
    missing = re.escape(str(registry.ROOT / "perfbench/archs/no_such.py"))
    with pytest.raises(FileNotFoundError, match=missing):
        registry.arch("no_such")
    with pytest.raises(FileNotFoundError, match=missing):
        weights.layer_spec({"model_type": "no_such"})
    with pytest.raises(FileNotFoundError, match=missing):
        registry.canonical({"model_type": "no_such"})
    cell = registry.cell(NAMES[0])
    hf, job = cell.hf, cell.job
    missing = re.escape(str(registry.ROOT / "perfbench/kernels/nokind.py"))
    with pytest.raises(FileNotFoundError, match=missing):
        counts.kernel_cost(hf, job, "nokind_fwd")
    with pytest.raises(ValueError, match="flash.py"):
        counts.kernel_cost(hf, job, "flash_nothing")
