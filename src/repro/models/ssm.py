"""State-space / linear-recurrence blocks: Mamba-2 (SSD) and RG-LRU (Griffin).

Pure-jnp chunked implementations (the scan over chunks keeps peak memory at
one chunk per layer); the Pallas kernels in ``repro.kernels.ssd`` /
``repro.kernels.rglru`` implement the same math with VMEM tiling and are
validated against these functions.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.config import LRUConfig, ModelConfig, SSMConfig
from repro.models.layers import dense_init, init_rms_norm, rms_norm

Array = jax.Array
Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Shared: causal depthwise conv1d
# ---------------------------------------------------------------------------

def causal_conv(x: Array, w: Array, b: Array) -> Array:
    """x: (B, S, C); w: (K, C) depthwise; left-padded causal conv."""
    K = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    out = jnp.zeros_like(x, dtype=jnp.float32)
    for k in range(K):
        out = out + xp[:, k:k + x.shape[1]].astype(jnp.float32) * w[k].astype(jnp.float32)
    return (out + b.astype(jnp.float32)).astype(x.dtype)


def conv_step(xt: Array, conv_state: Array, w: Array, b: Array
              ) -> Tuple[Array, Array]:
    """One-token causal conv.  xt: (B, C); conv_state: (B, K-1, C)."""
    K = w.shape[0]
    window = jnp.concatenate([conv_state, xt[:, None]], axis=1)   # (B, K, C)
    out = jnp.einsum("bkc,kc->bc", window.astype(jnp.float32),
                     w.astype(jnp.float32)) + b.astype(jnp.float32)
    return out.astype(xt.dtype), window[:, 1:]


# ---------------------------------------------------------------------------
# Mamba-2 (SSD)
# ---------------------------------------------------------------------------

def init_mamba2(key, cfg: ModelConfig, dtype) -> Params:
    s: SSMConfig = cfg.ssm
    D = cfg.d_model
    d_in = s.expand * D
    H = d_in // s.head_dim
    G, N = s.n_groups, s.d_state
    conv_dim = d_in + 2 * G * N
    keys = jax.random.split(key, 4)
    return {
        "in_proj": dense_init(keys[0], (D, 2 * d_in + 2 * G * N + H), 0, dtype),
        "conv_w": (jax.random.normal(keys[1], (s.d_conv, conv_dim), jnp.float32)
                   * (1.0 / math.sqrt(s.d_conv))).astype(dtype),
        "conv_b": jnp.zeros((conv_dim,), dtype),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, H)).astype(jnp.float32),
        "D": jnp.ones((H,), jnp.float32),
        "dt_bias": jnp.log(jnp.expm1(
            jnp.exp(jax.random.uniform(keys[2], (H,), jnp.float32,
                                       math.log(1e-3), math.log(1e-1))))),
        "norm": init_rms_norm(d_in, dtype),
        "out_proj": dense_init(keys[3], (d_in, D), 0, dtype),
    }


def _mamba2_split(p: Params, x: Array, cfg: ModelConfig):
    s: SSMConfig = cfg.ssm
    d_in = s.expand * cfg.d_model
    H = d_in // s.head_dim
    G, N = s.n_groups, s.d_state
    zxbcdt = x @ p["in_proj"]
    z, xbc, dt = jnp.split(zxbcdt, [d_in, 2 * d_in + 2 * G * N], axis=-1)
    return z, xbc, dt, d_in, H, G, N


def _ssd_scan(xh: Array, dA: Array, Bm: Array, Cm: Array, state0: Array,
              chunk: int):
    """Chunked SSD.  xh: (B,S,H,P) inputs pre-multiplied by dt; dA: (B,S,H);
    Bm, Cm: (B,S,H,N) (already broadcast over groups).  Returns (y, state)."""
    B_, S, H, P = xh.shape
    N = Bm.shape[-1]
    Q = min(chunk, S)
    pad = (-S) % Q
    if pad:     # zero-input, zero-decay (exp(0)=1) padding leaves state fixed
        zpad = lambda t: jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        xh, dA, Bm, Cm = zpad(xh), zpad(dA), zpad(Bm), zpad(Cm)
    Sp = S + pad
    nc = Sp // Q
    rs = lambda t: t.reshape((B_, nc, Q) + t.shape[2:]).swapaxes(0, 1)
    xc, dAc, Bc, Cc = rs(xh), rs(dA), rs(Bm), rs(Cm)

    dt = xh.dtype   # compute/storage dtype of the big tensors (bf16 at
    #                 full scale, f32 in tests); decays/state stay f32

    def body(state, xs):
        xq, dq, bq, cq = xs                     # (B,Q,H,P),(B,Q,H),(B,Q,H,N)
        csum = jnp.cumsum(dq, axis=1)           # (B,Q,H) f32
        # intra-chunk lower-triangular decays
        L = jnp.exp(csum[:, :, None] - csum[:, None, :])          # (B,Q,Q,H)
        tri = jnp.tril(jnp.ones((Q, Q), bool))
        L = jnp.where(tri[None, :, :, None], L, 0.0)
        scores = jnp.einsum("blhn,bshn->blsh", cq, bq,
                            preferred_element_type=jnp.float32)
        # the (B,Q,Q,H) product materializes once, in the storage dtype
        y = jnp.einsum("blsh,bshp->blhp", (scores * L).astype(dt), xq,
                       preferred_element_type=jnp.float32)
        # inter-chunk contribution
        y = y + jnp.einsum("blhn,bhpn->blhp", cq.astype(jnp.float32), state,
                           preferred_element_type=jnp.float32) \
              * jnp.exp(csum)[..., None]
        # end-of-chunk state
        decay = jnp.exp(csum[:, -1:, :] - csum)                   # (B,Q,H)
        new_state = state * jnp.exp(csum[:, -1])[..., None, None] \
            + jnp.einsum("bshn,bshp,bsh->bhpn", bq.astype(jnp.float32),
                         xq.astype(jnp.float32), decay,
                         preferred_element_type=jnp.float32)
        return new_state, y.astype(dt)

    body = jax.checkpoint(body)
    state, ys = lax.scan(body, state0, (xc, dAc, Bc, Cc))
    y = ys.swapaxes(0, 1).reshape(B_, Sp, H, P)[:, :S]
    return y, state


def _use_pallas_ssd(cfg: ModelConfig, S: int, P: int, N: int) -> bool:
    """Route the train/prefill scan through the Pallas SSD kernel?

    Mirrors ``layers._pallas_attention``: opt-in via ``cfg.use_pallas``.
    On TPU a shape that misses MXU-friendly tiling raises, naming the
    shape, instead of quietly taking the jnp scan (interpret mode on
    other backends handles any shape).
    """
    if not cfg.use_pallas:
        return False
    Q = min(cfg.ssm.chunk, S)
    if jax.default_backend() == "tpu" and (Q % 8 or P % 8 or N % 128):
        raise ValueError(
            f"use_pallas: the SSD kernel cannot tile chunk={Q}, "
            f"head_dim={P}, d_state={N} on TPU (needs chunk and head_dim "
            f"multiples of 8, d_state a multiple of 128)")
    return True


def _use_pallas_rglru(cfg: ModelConfig, S: int, W: int) -> bool:
    """As :func:`_use_pallas_ssd`, for the RG-LRU scan kernel."""
    if not cfg.use_pallas:
        return False
    Q = min(cfg.lru.block_width, S)
    if jax.default_backend() == "tpu" and (Q % 8 or W % 128):
        raise ValueError(
            f"use_pallas: the RG-LRU kernel cannot tile block_width={Q}, "
            f"lru_width={W} on TPU (needs a block_width multiple of 8 and "
            f"an lru_width multiple of 128)")
    return True


def mamba2_core(p: Params, x: Array, cfg: ModelConfig):
    """Shared train/prefill path.  x: (B,S,D) -> (y, final_state, conv_tail)."""
    s: SSMConfig = cfg.ssm
    B_, S, D = x.shape
    z, xbc, dt, d_in, H, G, N = _mamba2_split(p, x, cfg)
    xbc_conv = jax.nn.silu(causal_conv(xbc, p["conv_w"], p["conv_b"]))
    xs, Bm, Cm = jnp.split(xbc_conv, [d_in, d_in + G * N], axis=-1)
    P = s.head_dim
    xh = xs.reshape(B_, S, H, P)
    Bm = Bm.reshape(B_, S, G, N)
    Cm = Cm.reshape(B_, S, G, N)
    rep = H // G
    Bm = jnp.repeat(Bm, rep, axis=2)
    Cm = jnp.repeat(Cm, rep, axis=2)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])   # (B,S,H)
    A = -jnp.exp(p["A_log"])                                      # (H,)
    dA = dt * A
    # big tensors stay in the storage dtype (decays/state are f32 inside)
    if _use_pallas_ssd(cfg, S, P, N):
        from repro.kernels import ops as _K
        y, state = _K.ssd(xh * dt[..., None].astype(xh.dtype), dA,
                          Bm, Cm, chunk=s.chunk)
        y = y.astype(xh.dtype)
    else:
        state0 = jnp.zeros((B_, H, P, N), jnp.float32)
        y, state = _ssd_scan(xh * dt[..., None].astype(xh.dtype), dA,
                             Bm, Cm, state0, s.chunk)
    y = y + (p["D"].astype(xh.dtype)[None, None, :, None] * xh)
    y = y.reshape(B_, S, d_in).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z), p["norm"], cfg.norm_eps)
    out = y @ p["out_proj"]
    conv_tail = xbc[:, -(s.d_conv - 1):]  # pre-activation conv window tail
    return out, state, conv_tail


def mamba2_fwd(p: Params, x: Array, cfg: ModelConfig) -> Array:
    out, _, _ = mamba2_core(p, x, cfg)
    return out


def mamba2_prefill(p: Params, x: Array, cfg: ModelConfig, cache: Params
                   ) -> Tuple[Array, Params]:
    out, state, conv_tail = mamba2_core(p, x, cfg)
    return out, {"state": state.astype(cache["state"].dtype),
                 "conv": conv_tail.astype(cache["conv"].dtype)}


def mamba2_decode(p: Params, x: Array, cfg: ModelConfig, cache: Params
                  ) -> Tuple[Array, Params]:
    """One-token step.  x: (B, 1, D)."""
    s: SSMConfig = cfg.ssm
    B_, _, D = x.shape
    z, xbc, dt, d_in, H, G, N = _mamba2_split(p, x[:, 0:1], cfg)
    z, xbc, dt = z[:, 0], xbc[:, 0], dt[:, 0]
    conv_out, new_conv = conv_step(xbc, cache["conv"].astype(xbc.dtype),
                                   p["conv_w"], p["conv_b"])
    xbc_c = jax.nn.silu(conv_out)
    xs, Bm, Cm = jnp.split(xbc_c, [d_in, d_in + G * N], axis=-1)
    P = s.head_dim
    xh = xs.reshape(B_, H, P).astype(jnp.float32)
    Bm = jnp.repeat(Bm.reshape(B_, G, N), H // G, axis=1).astype(jnp.float32)
    Cm = jnp.repeat(Cm.reshape(B_, G, N), H // G, axis=1).astype(jnp.float32)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])   # (B,H)
    A = -jnp.exp(p["A_log"])
    dA = jnp.exp(dt * A)                                          # (B,H)
    state = cache["state"].astype(jnp.float32)
    state = state * dA[..., None, None] + \
        jnp.einsum("bhp,bhn,bh->bhpn", xh, Bm, dt)
    y = jnp.einsum("bhn,bhpn->bhp", Cm, state) + p["D"][None, :, None] * xh
    y = y.reshape(B_, 1, d_in).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z[:, None]), p["norm"], cfg.norm_eps)
    return y @ p["out_proj"], {"state": state.astype(cache["state"].dtype),
                               "conv": new_conv.astype(cache["conv"].dtype)}


def init_mamba2_cache(cfg: ModelConfig, batch: int, dtype) -> Params:
    s: SSMConfig = cfg.ssm
    d_in = s.expand * cfg.d_model
    H = d_in // s.head_dim
    conv_dim = d_in + 2 * s.n_groups * s.d_state
    return {
        "state": jnp.zeros((batch, H, s.head_dim, s.d_state), jnp.float32),
        "conv": jnp.zeros((batch, s.d_conv - 1, conv_dim), dtype),
    }


# ---------------------------------------------------------------------------
# RG-LRU (Griffin / RecurrentGemma recurrent block)
# ---------------------------------------------------------------------------

C_SCALE = 8.0   # Griffin's fixed c constant


def init_rglru(key, cfg: ModelConfig, dtype) -> Params:
    l: LRUConfig = cfg.lru
    D = cfg.d_model
    W = l.lru_width or D
    keys = jax.random.split(key, 6)
    # Lambda parametrized so a = sigmoid(lam)^(c*r) starts near 0.9..0.999
    u = jax.random.uniform(keys[0], (W,), jnp.float32, 0.9, 0.999)
    lam = jnp.log(u ** 2 / (1 - u ** 2))   # logit of a^2's sqrt-param
    return {
        "in_x": dense_init(keys[1], (D, W), 0, dtype),
        "in_z": dense_init(keys[2], (D, W), 0, dtype),
        "conv_w": (jax.random.normal(keys[3], (l.d_conv, W), jnp.float32)
                   * (1.0 / math.sqrt(l.d_conv))).astype(dtype),
        "conv_b": jnp.zeros((W,), dtype),
        "lam": lam,
        "wa": dense_init(keys[4], (W, W), 0, dtype),
        "ba": jnp.zeros((W,), jnp.float32),
        "wx": dense_init(keys[5], (W, W), 0, dtype),
        "bx": jnp.zeros((W,), jnp.float32),
        "out_proj": dense_init(jax.random.fold_in(key, 7), (W, D), 0, dtype),
    }


def _rglru_gates(p: Params, xw: Array):
    """a_t, gated input.  xw: (..., W) post-conv branch activations (f32)."""
    r = jax.nn.sigmoid(xw @ p["wa"].astype(xw.dtype) + p["ba"])
    i = jax.nn.sigmoid(xw @ p["wx"].astype(xw.dtype) + p["bx"])
    log_a = -C_SCALE * jax.nn.softplus(-p["lam"]) * r       # log sigmoid(lam)*c*r
    a = jnp.exp(log_a)
    gated = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-6)) * (i * xw)
    return a, gated


def _lru_scan(a: Array, b: Array, h0: Array, chunk: int):
    """h_t = a_t h_{t-1} + b_t, chunked.  a, b: (B,S,W) f32; h0: (B,W)."""
    B_, S, W = a.shape
    Q = min(chunk, S)
    pad = (-S) % Q
    if pad:     # a=1, b=0 padding leaves the state fixed
        a = jnp.pad(a, ((0, 0), (0, pad), (0, 0)), constant_values=1.0)
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0)))
    Sp = S + pad
    nc = Sp // Q
    rs = lambda t: t.reshape(B_, nc, Q, W).swapaxes(0, 1)
    ac, bc = rs(a), rs(b)

    def combine(x, y):
        a1, b1 = x
        a2, b2 = y
        return a1 * a2, a2 * b1 + b2

    def body(h, xs):
        aq, bq = xs
        A, Bv = lax.associative_scan(combine, (aq, bq), axis=1)
        hq = A * h[:, None] + Bv
        return hq[:, -1], hq

    body = jax.checkpoint(body)
    h, ys = lax.scan(body, h0, (ac, bc))
    ys = ys.swapaxes(0, 1).reshape(B_, Sp, W)[:, :S]
    return ys, ys[:, -1] if pad else h


def rglru_core(p: Params, x: Array, cfg: ModelConfig):
    l: LRUConfig = cfg.lru
    B_, S, D = x.shape
    W = l.lru_width or D
    z = jax.nn.gelu(x @ p["in_z"])
    xb = x @ p["in_x"]
    xc = jax.nn.silu(causal_conv(xb, p["conv_w"], p["conv_b"]))
    xf = xc.astype(jnp.float32)
    a, gated = _rglru_gates(p, xf)
    if _use_pallas_rglru(cfg, S, W):
        from repro.kernels import ops as _K
        h = _K.rglru(a, gated, chunk=l.block_width)
        hT = h[:, -1]
    else:
        h0 = jnp.zeros((B_, W), jnp.float32)
        h, hT = _lru_scan(a, gated, h0, l.block_width)
    y = (h.astype(x.dtype) * z) @ p["out_proj"]
    conv_tail = xb[:, -(l.d_conv - 1):]
    return y, hT, conv_tail


def rglru_fwd(p: Params, x: Array, cfg: ModelConfig) -> Array:
    y, _, _ = rglru_core(p, x, cfg)
    return y


def rglru_prefill(p: Params, x: Array, cfg: ModelConfig, cache: Params
                  ) -> Tuple[Array, Params]:
    y, hT, conv_tail = rglru_core(p, x, cfg)
    return y, {"state": hT, "conv": conv_tail.astype(cache["conv"].dtype)}


def rglru_decode(p: Params, x: Array, cfg: ModelConfig, cache: Params
                 ) -> Tuple[Array, Params]:
    l: LRUConfig = cfg.lru
    B_ = x.shape[0]
    z = jax.nn.gelu(x[:, 0] @ p["in_z"])
    xb = x[:, 0] @ p["in_x"]
    conv_out, new_conv = conv_step(xb, cache["conv"].astype(xb.dtype),
                                   p["conv_w"], p["conv_b"])
    xf = jax.nn.silu(conv_out).astype(jnp.float32)
    a, gated = _rglru_gates(p, xf)
    h = a * cache["state"] + gated
    y = ((h.astype(x.dtype) * z) @ p["out_proj"])[:, None]
    return y, {"state": h, "conv": new_conv.astype(cache["conv"].dtype)}


def init_rglru_cache(cfg: ModelConfig, batch: int, dtype) -> Params:
    l: LRUConfig = cfg.lru
    W = l.lru_width or cfg.d_model
    return {
        "state": jnp.zeros((batch, W), jnp.float32),
        "conv": jnp.zeros((batch, l.d_conv - 1, W), dtype),
    }
