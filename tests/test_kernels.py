"""Pallas kernel validation: shape/dtype sweeps against the pure-jnp
oracles in kernels/ref.py (interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.rglru import rglru_scan
from repro.kernels.ssd import ssd_scan

TOLS = {jnp.float32: dict(rtol=2e-5, atol=2e-5),
        jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


@pytest.mark.parametrize("B,Sq,Sk,H,K,D", [
    (2, 256, 256, 4, 2, 64),
    (1, 128, 128, 4, 4, 32),
    (2, 128, 128, 8, 1, 64),     # MQA
    (1, 512, 512, 2, 2, 128),
])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, Sq, Sk, H, K, D, causal, dtype):
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (B, Sq, H, D), dtype)
    k = jax.random.normal(ks[1], (B, Sk, K, D), dtype)
    v = jax.random.normal(ks[2], (B, Sk, K, D), dtype)
    out = flash_attention_fwd(q, k, v, causal=causal,
                              q_block=64, kv_block=64, interpret=True)
    want = ref.attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **TOLS[dtype])


@pytest.mark.parametrize("window", [32, 64, 128])
def test_flash_attention_window(window):
    ks = jax.random.split(jax.random.key(1), 3)
    B, S, H, K, D = 1, 256, 2, 2, 64
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, K, D))
    v = jax.random.normal(ks[2], (B, S, K, D))
    out = flash_attention_fwd(q, k, v, causal=True, window=window,
                              q_block=64, kv_block=64, interpret=True)
    want = ref.attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("B,S,H,P,N,chunk", [
    (2, 128, 3, 16, 32, 32),
    (1, 256, 2, 32, 16, 64),
    (2, 64, 1, 8, 8, 64),
    (1, 512, 4, 16, 16, 128),
])
def test_ssd_sweep(B, S, H, P, N, chunk):
    ks = jax.random.split(jax.random.key(2), 4)
    xdt = jax.random.normal(ks[0], (B, S, H, P)) * 0.5
    dA = -jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    Bm = jax.random.normal(ks[2], (B, S, H, N)) * 0.3
    Cm = jax.random.normal(ks[3], (B, S, H, N)) * 0.3
    y, state = ssd_scan(xdt, dA, Bm, Cm, chunk=chunk, interpret=True)
    want = ref.ssd_ref(xdt, dA, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_ssd_final_state_matches_sequential():
    ks = jax.random.split(jax.random.key(3), 4)
    B, S, H, P, N = 1, 128, 2, 8, 8
    xdt = jax.random.normal(ks[0], (B, S, H, P)) * 0.5
    dA = -jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    Bm = jax.random.normal(ks[2], (B, S, H, N)) * 0.3
    Cm = jax.random.normal(ks[3], (B, S, H, N)) * 0.3
    _, state = ssd_scan(xdt, dA, Bm, Cm, chunk=32, interpret=True)

    def step(h, inp):
        x_t, dA_t, b_t = inp
        return h * jnp.exp(dA_t)[..., None, None] + \
            jnp.einsum("bhn,bhp->bhpn", b_t, x_t), None
    h0 = jnp.zeros((B, H, P, N))
    want, _ = jax.lax.scan(step, h0, (xdt.swapaxes(0, 1), dA.swapaxes(0, 1),
                                      Bm.swapaxes(0, 1)))
    np.testing.assert_allclose(np.asarray(state), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("B,S,W,chunk,wb", [
    (2, 128, 64, 32, 32),
    (1, 256, 128, 64, 64),
    (3, 64, 32, 64, 32),
    (1, 512, 64, 128, 64),
])
def test_rglru_sweep(B, S, W, chunk, wb):
    ks = jax.random.split(jax.random.key(4), 2)
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (B, S, W))) * 0.98
    b = jax.random.normal(ks[1], (B, S, W)) * 0.5
    y = rglru_scan(a, b, chunk=chunk, width_block=wb, interpret=True)
    want = ref.rglru_ref(a, b)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_model_ssd_matches_kernel_math():
    """The model-side chunked SSD (models/ssm.py) and the kernel agree."""
    from repro.models.ssm import _ssd_scan
    ks = jax.random.split(jax.random.key(5), 4)
    B, S, H, P, N = 2, 128, 2, 8, 16
    xdt = jax.random.normal(ks[0], (B, S, H, P)) * 0.5
    dA = -jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    Bm = jax.random.normal(ks[2], (B, S, H, N)) * 0.3
    Cm = jax.random.normal(ks[3], (B, S, H, N)) * 0.3
    y_model, st_model = _ssd_scan(xdt, dA, Bm, Cm,
                                  jnp.zeros((B, H, P, N)), 32)
    y_kern, st_kern = ssd_scan(xdt, dA, Bm, Cm, chunk=32, interpret=True)
    np.testing.assert_allclose(np.asarray(y_model), np.asarray(y_kern),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(st_model), np.asarray(st_kern),
                               rtol=2e-4, atol=2e-4)


def test_model_lru_matches_kernel():
    from repro.models.ssm import _lru_scan
    ks = jax.random.split(jax.random.key(6), 2)
    B, S, W = 2, 128, 32
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (B, S, W))) * 0.98
    b = jax.random.normal(ks[1], (B, S, W)) * 0.5
    y_model, _ = _lru_scan(a, b, jnp.zeros((B, W)), 32)
    y_kern = rglru_scan(a, b, chunk=32, width_block=32, interpret=True)
    np.testing.assert_allclose(np.asarray(y_model), np.asarray(y_kern),
                               rtol=1e-5, atol=1e-5)


def test_pallas_attention_refuses_untileable_sequence():
    """With ``use_pallas`` a sequence that is not a whole number of kernel
    blocks raises, naming the shape, instead of taking the blockwise
    path."""
    from repro.models.layers import _pallas_attention
    q = jnp.zeros((1, 192, 4, 16))
    kv = jnp.zeros((1, 192, 2, 16))
    with pytest.raises(ValueError, match=r"\(1, 192, 4, 16\)"):
        _pallas_attention(q, kv, kv, causal=True, window=0)


@pytest.mark.parametrize("arch", ["mamba2-2.7b", "recurrentgemma-2b"])
def test_pallas_scans_refuse_untileable_shapes_on_tpu(arch, monkeypatch):
    """On TPU a scan width Mosaic cannot tile raises instead of quietly
    running the jnp scan; the reduced widths (16 / 64) are such shapes.
    The published widths pass the same check."""
    import dataclasses

    from repro.configs import get_config, reduced_config
    from repro.models import ssm
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def route(cfg):
        cfg = dataclasses.replace(cfg, use_pallas=True)
        if arch.startswith("mamba2"):
            return ssm._use_pallas_ssd(cfg, 2048, cfg.ssm.head_dim,
                                       cfg.ssm.d_state)
        return ssm._use_pallas_rglru(cfg, 2048, cfg.lru.lru_width)

    with pytest.raises(ValueError, match="use_pallas"):
        route(reduced_config(arch))
    assert route(get_config(arch))
