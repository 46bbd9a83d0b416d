"""Pallas kernel validation: shape/dtype sweeps against the pure-jnp
oracles in kernels/ref.py (interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import (flash_attention_fwd, flash_blocks,
                                           kv_band, pair_mask, q_band,
                                           tile_full, tile_visible)
from repro.kernels.rglru import rglru_scan
from repro.kernels.ssd import ssd_scan

TOLS = {jnp.float32: dict(rtol=2e-5, atol=2e-5),
        jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


@pytest.mark.parametrize("B,Sq,Sk,H,K,D,q_block,kv_block", [
    (2, 256, 256, 4, 2, 64, 64, 64),
    (1, 128, 128, 4, 4, 32, 64, 64),
    (2, 128, 128, 8, 1, 64, 64, 64),     # MQA
    (1, 512, 512, 2, 2, 128, 64, 64),
    # GQA with G=8 and unequal tiles: 4 x 8 and 8 x 4 tiles, so fully
    # visible, diagonal and skipped (clamped-copy) tiles all occur
    (1, 512, 512, 16, 2, 64, 128, 64),
    (1, 512, 512, 16, 2, 64, 64, 128),
    (1, 256, 256, 4, 2, 64, None, None),   # the blocks flash_blocks picks
])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, Sq, Sk, H, K, D, q_block, kv_block,
                               causal, dtype):
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (B, Sq, H, D), dtype)
    k = jax.random.normal(ks[1], (B, Sk, K, D), dtype)
    v = jax.random.normal(ks[2], (B, Sk, K, D), dtype)
    out = flash_attention_fwd(q, k, v, causal=causal, q_block=q_block,
                              kv_block=kv_block, interpret=True)
    want = ref.attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **TOLS[dtype])


@pytest.mark.parametrize("window,q_block,kv_block,dtype", [
    (32, 64, 64, jnp.float32),
    (64, 64, 64, jnp.float32),
    (128, 64, 64, jnp.float32),
    # windows that are no multiple of the tiles: window-edge tiles inside
    # the band, skipped tiles on both sides of it
    (96, 64, 128, jnp.float32),
    (96, 128, 64, jnp.bfloat16),
    (200, 128, 64, jnp.float32),
])
def test_flash_attention_window(window, q_block, kv_block, dtype):
    ks = jax.random.split(jax.random.key(1), 3)
    B, S, H, K, D = 1, 256, 2, 2, 64
    q = jax.random.normal(ks[0], (B, S, H, D), dtype)
    k = jax.random.normal(ks[1], (B, S, K, D), dtype)
    v = jax.random.normal(ks[2], (B, S, K, D), dtype)
    out = flash_attention_fwd(q, k, v, causal=True, window=window,
                              q_block=q_block, kv_block=kv_block,
                              interpret=True)
    want = ref.attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **TOLS[dtype])


@pytest.mark.parametrize("kind", ["fwd", "dq", "dkv"])
def test_flash_blocks_divide_and_clamp(kind):
    """The chosen tiles divide the sequence, clamp to it when it is
    shorter than a tile, and at sequences that are multiples of 128 (all
    the TPU takes) are multiples of 128 themselves."""
    for S in (8, 64, 96, 128, 256, 384, 640, 1024, 2048, 4096, 32768):
        qb, kb = flash_blocks(S, S, kind)
        assert S % qb == 0 and S % kb == 0, (S, qb, kb)
        if S <= 128:
            assert (qb, kb) == (S, S)
        if S % 128 == 0:
            assert qb % 128 == 0 and kb % 128 == 0, (S, qb, kb)
    # cross-shaped: each side clamps to its own length
    assert flash_blocks(64, 4096, kind)[0] == 64
    assert flash_blocks(4096, 64, kind)[1] == 64


@pytest.mark.parametrize("q_block,kv_block,causal,window", [
    (64, 64, True, 0),
    (128, 64, True, 0),
    (64, 128, True, 96),
    (128, 64, False, 200),
    (64, 64, False, 0),
])
def test_flash_tile_bands_match_the_mask(q_block, kv_block, causal, window):
    """The clamped index maps load exactly the visible tiles: kv_band /
    q_band hold a block iff ``tile_visible`` says the tile has a visible
    pair, and ``tile_full`` holds iff every pair of ``pair_mask`` is."""
    S = 512
    nq, nk = S // q_block, S // kv_block
    for i in range(nq):
        qs = i * q_block
        first, last = (int(x) for x in kv_band(qs, q_block, kv_block, nk,
                                              causal, window))
        for j in range(nk):
            ks = j * kv_block
            mask = np.asarray(pair_mask((q_block, kv_block), qs, ks, causal,
                                        window))
            visible = bool(tile_visible(qs, ks, q_block, kv_block, causal,
                                        window))
            assert visible == mask.any() == (first <= j <= last), (i, j)
            assert bool(tile_full(qs, ks, q_block, kv_block, causal,
                                  window)) == mask.all(), (i, j)
            qf, ql = (int(x) for x in q_band(ks, q_block, kv_block, nq,
                                            causal, window))
            assert visible == (qf <= i <= ql), (i, j)


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
    blocks raises, naming the shape and the blocks chosen, instead of
    taking the blockwise path.  1088 is longer than any preferred tile and
    no multiple of 128; a sequence shorter than the tile is one block."""
    from repro.models.layers import _pallas_attention
    q = jnp.zeros((1, 1088, 4, 16))
    kv = jnp.zeros((1, 1088, 2, 16))
    with pytest.raises(ValueError,
                       match=r"\(1, 1088, 4, 16\).*'fwd': \(128, 128\)"):
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
