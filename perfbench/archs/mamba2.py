"""Mamba-2 (``model_type`` ``mamba2``; arXiv:2405.21060).

Pre-norm Mamba-2 blocks: in-projection to z, x, B, C, dt; causal
depthwise conv with bias and SiLU over x, B, C; softplus dt with bias;
A = -exp(A_log); SSD; skip D; gated RMSNorm of y * silu(z);
out-projection.  The reference's SSD is the chunked "minimal" algorithm
of the Mamba-2 paper (listing 1), in float32; the counts take its
chunked algorithm's matmuls (intra-chunk (Q x Q) terms, chunk states,
inter-chunk output).
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from perfbench import counts
from perfbench.reference import F32, ein, rms

#: mamba_ssm's config.json key names and the Llama-style names the
#: benchmark reads for them.
ALIASES = {"d_model": "hidden_size", "n_layer": "num_hidden_layers",
           "tie_embeddings": "tie_word_embeddings",
           "norm_epsilon": "rms_norm_eps"}


# -- weights ------------------------------------------------------------------

def init_conv(key, shape):
    return jax.random.normal(key, shape, F32) / math.sqrt(shape[-2])


def init_a_log(key, shape):
    return jnp.log(jax.random.uniform(key, shape, F32, 1.0, 16.0))


def init_skip(key, shape):
    return jax.random.uniform(key, shape, F32, 0.5, 1.5)


def init_dt_bias(key, shape):
    """softplus^-1 of dt ~ log-uniform [1e-3, 1e-1]."""
    dt = jnp.exp(jax.random.uniform(key, shape, F32, math.log(1e-3),
                                    math.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))


def layer_spec(hf: Dict[str, Any]) -> Dict[str, tuple]:
    """Per-layer leaves: path -> (shape, dtype, init rule)."""
    dtype = hf["torch_dtype"]
    d = hf["hidden_size"]
    s = hf["ssm_cfg"]
    d_in = s["expand"] * d
    nh = d_in // s["headdim"]
    gn = s["ngroups"] * s["d_state"]
    width = d_in + 2 * gn
    return {
        "ln1": ((d,), dtype, "gain"),
        "mixer.in_proj": ((d, 2 * d_in + 2 * gn + nh), dtype,
                          "fan_in"),
        "mixer.conv_w": ((s["d_conv"], width), dtype, init_conv),
        "mixer.conv_b": ((width,), dtype, "small"),
        "mixer.A_log": ((nh,), "float32", init_a_log),
        "mixer.D": ((nh,), "float32", init_skip),
        "mixer.dt_bias": ((nh,), "float32", init_dt_bias),
        "mixer.norm": ((d_in,), dtype, "gain"),
        "mixer.out_proj": ((d_in, d), dtype, "fan_in"),
    }


# -- the float32 reference ----------------------------------------------------

def segsum(a):
    """Stable segment sums: out[..., i, j] = sum(a[..., j+1 : i+1]) for
    j <= i, -inf above the diagonal.  a: (..., T)."""
    t = a.shape[-1]
    x = jnp.repeat(a[..., None], t, axis=-1)                # (..., T, T)
    x = jnp.where(jnp.tril(jnp.ones((t, t), bool), -1), x, 0.0)
    x = jnp.cumsum(x, axis=-2)
    return jnp.where(jnp.tril(jnp.ones((t, t), bool), 0), x, -jnp.inf)


def ssd(x, a, bm, cm, block, low):
    """SSD, chunked minimal form.  x: (B, S, H, P) (already times dt);
    a: (B, S, H) (dt * A); bm, cm: (B, S, H, N).  Returns y (B, S, H, P)."""
    b_, s, h, p = x.shape
    c = s // block
    r = lambda t: t.reshape((b_, c, block) + t.shape[2:])  # noqa: E731
    x, a, bm, cm = r(x), r(a), r(bm), r(cm)
    a = jnp.moveaxis(a, -1, 1)                               # (B, H, C, L)
    a_cum = jnp.cumsum(a, axis=-1)
    lmat = jnp.exp(segsum(a))                                # (B,H,C,L,L)
    y_diag = ein("bclhn,bcshn,bhcls,bcshp->bclhp", cm, bm, lmat, x, low=low)
    decay = jnp.exp(a_cum[..., -1:] - a_cum)                 # (B,H,C,L)
    states = ein("bclhn,bhcl,bclhp->bchpn", bm, decay, x, low=low)
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], 1)
    chunk_decay = jnp.exp(segsum(jnp.pad(a_cum[..., -1], ((0, 0), (0, 0),
                                                           (1, 0)))))
    new_states = ein("bhzc,bchpn->bzhpn", chunk_decay, states, low=low)
    states = new_states[:, :-1]
    y_off = ein("bclhn,bchpn,bhcl->bclhp", cm, states, jnp.exp(a_cum),
                low=low)
    return (y_diag + y_off).reshape(b_, s, h, p)


def layer(p, x, hf, low):
    cfg = hf["ssm_cfg"]
    b, s, d = x.shape
    d_in = cfg["expand"] * d
    hp = cfg["headdim"]
    nh = d_in // hp
    gn = cfg["ngroups"] * cfg["d_state"]
    eps = hf["rms_norm_eps"]
    m = p["mixer"]
    u = rms(x, p["ln1"], eps)
    zxbcdt = ein("bsd,de->bse", u, m["in_proj"], low=low)
    z = zxbcdt[..., :d_in]
    xbc = zxbcdt[..., d_in:2 * d_in + 2 * gn]
    dt = zxbcdt[..., 2 * d_in + 2 * gn:]
    kc = cfg["d_conv"]
    w = m["conv_w"].astype(F32)
    xp = jnp.pad(xbc, ((0, 0), (kc - 1, 0), (0, 0)))
    conv = sum(xp[:, i:i + s] * w[i] for i in range(kc))
    xbc = jax.nn.silu(conv + m["conv_b"].astype(F32))
    xs = xbc[..., :d_in].reshape(b, s, nh, hp)
    n = cfg["d_state"]
    g = cfg["ngroups"]
    bm = jnp.repeat(xbc[..., d_in:d_in + gn].reshape(b, s, g, n), nh // g, 2)
    cm = jnp.repeat(xbc[..., d_in + gn:].reshape(b, s, g, n), nh // g, 2)
    dt = jax.nn.softplus(dt + m["dt_bias"])
    a = -jnp.exp(m["A_log"])
    y = ssd(xs * dt[..., None], dt * a, bm, cm, cfg["chunk_size"], low)
    y = y + m["D"][None, None, :, None] * xs
    y = rms(y.reshape(b, s, d_in) * jax.nn.silu(z), m["norm"], eps)
    return x + ein("bse,ed->bsd", y, m["out_proj"], low=low)


# -- counts -------------------------------------------------------------------

def layer_flops(hf, job) -> Dict[str, float]:
    """One layer's forward flops and those of its entry projections."""
    b, s, d = counts.dims(hf, job)
    t = b * s
    c = hf["ssm_cfg"]
    d_in = c["expand"] * d
    nh = d_in // c["headdim"]
    gn = c["ngroups"] * c["d_state"]
    p, n, q = c["headdim"], c["d_state"], c["chunk_size"]
    entry = d * (2 * d_in + 2 * gn + nh)
    mats = entry + d_in * d
    conv = 2 * t * c["d_conv"] * (d_in + 2 * gn)
    ssd = t * nh * (2 * q * (n + p) + 4 * n * p)
    return {"fwd_mm": 2 * t * mats + conv, "fwd_mixer": ssd,
            "entry_mm": 2 * t * entry}


# -- the trainer's config -----------------------------------------------------

def program_keys(hf: Dict[str, Any]) -> Dict[str, Any]:
    """The trainer's ModelConfig attributes this architecture checks, with
    the values the configuration file gives them."""
    s = hf["ssm_cfg"]
    return {"ssm.d_state": s["d_state"], "ssm.d_conv": s["d_conv"],
            "ssm.expand": s["expand"], "ssm.head_dim": s["headdim"],
            "ssm.chunk": s["chunk_size"], "ssm.n_groups": s["ngroups"]}


#: The CPU stand-in's widths: (published-key updates, trainer overrides).
#: In float32: the CPU backend sums the backward's bfloat16 reductions
#: (the per-head leaves' gradients: D, A_log, dt_bias) in bfloat16, so a
#: sound bfloat16 run there reads grad_gap 0.03-0.07, over what the chip
#: reads at full size (under 0.01); in float32 it reads about 2e-6.
STAND_IN = ({"d_model": 64, "vocab_size": 500, "torch_dtype": "float32",
             "ssm_cfg": {"d_state": 16, "headdim": 16, "chunk_size": 16}},
            {"d_model": 64, "vocab_size": 500, "use_pallas": False,
             "dtype": "float32",
             "ssm": {"d_state": 16, "d_conv": 4, "expand": 2,
                     "head_dim": 16, "chunk": 16}})
