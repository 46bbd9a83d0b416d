"""Main-path Pallas kernels compile for a described TPU v5e at published
widths, and ``chip_smoke.py`` refuses to run without a TPU.

Interpret mode (what the other kernel tests run) accepts block shapes and
primitives that Mosaic refuses, so each kernel is also lowered and
compiled here for a ``v5e:2x2`` topology that is described, not attached,
and its HLO must hold every kernel (``tpu_custom_call``).  Nothing runs:
these tests say nothing about results or times.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, and test workers
import every test file.
"""
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_count(fn, *args) -> int:
    """Compile for the described chip; count the Mosaic kernels in it."""
    return jax.jit(fn).lower(*args).compile().as_text().count(
        "tpu_custom_call")


def _fwd_and_grad(f, direction):
    """The forward itself, or the gradient of a scalar of it (which runs
    the residual-saving forward and every backward kernel)."""
    if direction == "fwd":
        return f

    def loss(*args):
        out = f(*args)
        out = out[0] if isinstance(out, tuple) else out
        return jnp.sum(out.astype(jnp.float32))
    return lambda *args: jax.grad(
        loss, argnums=tuple(range(len(args))))(*args)


# backward: the residual forward, then delta, dq and dk/dv
@pytest.mark.parametrize("direction,kernels,S,block", [
    ("fwd", 1, 2048, 128), ("bwd", 4, 2048, 128),
    # the benchmark's sequence with the tiles flash_blocks picks per kernel
    ("fwd", 1, 4096, None), ("bwd", 4, 4096, None),
])
def test_flash_attention_compiles_at_yi_6b_widths(one_chip, direction,
                                                  kernels, S, block):
    """yi-6b: 32 q heads, 4 kv heads, head_dim 128; 128 blocks at S=2048,
    the chosen ones at S=4096."""
    def f(q, k, v):
        return ops.flash_attention(q, k, v, causal=True, q_block=block,
                                   kv_block=block, interpret=False)
    q = _spec(one_chip, (1, S, 32, 128))
    kv = _spec(one_chip, (1, S, 4, 128))
    assert _kernel_count(_fwd_and_grad(f, direction), q, kv, kv) >= kernels


@pytest.mark.parametrize("direction,kernels", [("fwd", 1), ("bwd", 2)])
def test_ssd_compiles_at_mamba2_widths(one_chip, direction, kernels):
    """mamba2-2.7b: 80 heads x head_dim 64, d_state 128, chunk 256."""
    def f(xdt, dA, b, c):
        return ops.ssd(xdt, dA, b, c, chunk=256, interpret=False)
    B, S, H, P, N = 1, 2048, 80, 64, 128
    n = _kernel_count(_fwd_and_grad(f, direction),
                      _spec(one_chip, (B, S, H, P)),
                      _spec(one_chip, (B, S, H), jnp.float32),
                      _spec(one_chip, (B, S, H, N)),
                      _spec(one_chip, (B, S, H, N)))
    assert n >= kernels


@pytest.mark.parametrize("direction,kernels", [("fwd", 1), ("bwd", 2)])
def test_rglru_compiles_at_recurrentgemma_width(one_chip, direction,
                                                kernels):
    """recurrentgemma-2b: lru_width 2560, block_width 256."""
    def f(a, b):
        return ops.rglru(a, b, chunk=256, interpret=False)
    a = _spec(one_chip, (1, 2048, 2560), jnp.float32)
    assert _kernel_count(_fwd_and_grad(f, direction), a, a) >= kernels


def test_chip_smoke_refuses_cpu():
    """With no TPU the smoke run exits non-zero and claims nothing."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": str(REPO / "src")}
    r = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                       env=env, capture_output=True, text=True, timeout=300,
                       cwd=REPO)
    assert r.returncode != 0, r.stdout + r.stderr
    assert '"ok": true' not in r.stdout


def test_chip_smoke_train_phase_runs_at_reduced_size():
    """The smoke's session phase (build_engine -> SPBEngine, temporal SPB,
    AOT-compiled depths, Pipeline batches) runs end to end on the CPU at
    the reduced size: two depths, three finite steps each."""
    import importlib.util

    from repro.config import TrainConfig
    from repro.configs import reduced_config
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", REPO / "chip_smoke.py")
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    cfg = reduced_config("yi-6b").scaled(num_layers=2)
    res = smoke.train_phase(cfg, TrainConfig(),
                            smoke.one_device_mesh(jax.devices()[0]),
                            batch=2, seq=64, seed=0)
    assert [s["depth"] for s in res["steps"]] == [2, 1, 2, 1, 2, 1]
    assert sorted(res["kernels"]) == [1, 2]
