"""Llama-style decoder (Yi, ``model_type`` ``llama``).

Pre-norm decoder layers: RMSNorm, rotary embedding on the rotate-half
convention with the file's ``rope_theta``, grouped-query causal
attention, SwiGLU MLP.  Causal attention counts S(S+1)/2 query-key pairs
per head.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from perfbench import counts
from perfbench.reference import F32, ein, rms


def layer_spec(hf: Dict[str, Any]
               ) -> Dict[str, Tuple[Tuple[int, ...], str, str]]:
    """Per-layer leaves: path -> (shape, dtype, init rule)."""
    dtype = hf["torch_dtype"]
    d = hf["hidden_size"]
    h, kv = hf["num_attention_heads"], hf["num_key_value_heads"]
    dh = d // h
    f = hf["intermediate_size"]
    return {
        "ln1": ((d,), dtype, "gain"),
        "mixer.wq": ((d, h * dh), dtype, "fan_in"),
        "mixer.wk": ((d, kv * dh), dtype, "fan_in"),
        "mixer.wv": ((d, kv * dh), dtype, "fan_in"),
        "mixer.wo": ((h * dh, d), dtype, "fan_in"),
        "ln2": ((d,), dtype, "gain"),
        "ffn.wg": ((d, f), dtype, "fan_in"),
        "ffn.wu": ((d, f), dtype, "fan_in"),
        "ffn.wd": ((f, d), dtype, "fan_in"),
    }


# -- the float32 reference ----------------------------------------------------

def rope(x, theta):
    """Rotary embedding, rotate-half convention.  x: (B, S, H, Dh)."""
    s, dh = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=F32) / dh))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def causal_attention(q, k, v, low, q_block=512):
    """Grouped-query causal softmax attention, one block of queries at a
    time (a scan, so only one (B, heads, q_block, S) score block lives)."""
    b, s, h, dh = q.shape
    kvh = k.shape[2]
    g = h // kvh
    qb = min(q_block, s)
    q = q.reshape(b, s // qb, qb, kvh, g, dh) / math.sqrt(dh)
    kpos = jnp.arange(s)

    @jax.checkpoint
    def block(i, qblk):
        sc = ein("bqkgd,bskd->bkgqs", qblk, k, low=low)
        qpos = i * qb + jnp.arange(qb)
        sc = jnp.where(kpos[None, :] <= qpos[:, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return ein("bkgqs,bskd->bqkgd", p, v, low=low)

    out = lax.map(lambda a: block(*a), (jnp.arange(s // qb),
                                        jnp.moveaxis(q, 1, 0)))
    return jnp.moveaxis(out, 0, 1).reshape(b, s, h * dh)


def layer(p, x, hf, low):
    b, s, d = x.shape
    h, kvh = hf["num_attention_heads"], hf["num_key_value_heads"]
    dh = d // h
    eps = hf["rms_norm_eps"]
    a = rms(x, p["ln1"], eps)
    q = ein("bsd,de->bse", a, p["mixer"]["wq"], low=low).reshape(b, s, h, dh)
    k = ein("bsd,de->bse", a, p["mixer"]["wk"], low=low).reshape(b, s, kvh, dh)
    v = ein("bsd,de->bse", a, p["mixer"]["wv"], low=low).reshape(b, s, kvh, dh)
    q, k = rope(q, hf["rope_theta"]), rope(k, hf["rope_theta"])
    o = causal_attention(q, k, v, low)
    x = x + ein("bse,ed->bsd", o, p["mixer"]["wo"], low=low)
    m = rms(x, p["ln2"], eps)
    gate = ein("bsd,df->bsf", m, p["ffn"]["wg"], low=low)
    up = ein("bsd,df->bsf", m, p["ffn"]["wu"], low=low)
    return x + ein("bsf,fd->bsd", jax.nn.silu(gate) * up, p["ffn"]["wd"],
                   low=low)


# -- counts -------------------------------------------------------------------

def layer_flops(hf, job) -> Dict[str, float]:
    """One layer's forward flops and those of its entry projections."""
    b, s, d = counts.dims(hf, job)
    t = b * s
    h, kv = hf["num_attention_heads"], hf["num_key_value_heads"]
    dh = d // h
    entry = d * (h + 2 * kv) * dh
    mats = entry + h * dh * d + 3 * d * hf["intermediate_size"]
    mixer = 4 * b * h * dh * counts.pairs(s)          # QK^T and PV
    return {"fwd_mm": 2 * t * mats, "fwd_mixer": mixer,
            "entry_mm": 2 * t * entry}


# -- the trainer's config -----------------------------------------------------

def program_keys(hf: Dict[str, Any]) -> Dict[str, Any]:
    """The trainer's ModelConfig attributes this architecture checks, with
    the values the configuration file gives them."""
    return {"num_heads": hf["num_attention_heads"],
            "num_kv_heads": hf["num_key_value_heads"],
            "d_ff": hf["intermediate_size"],
            "rope_theta": hf["rope_theta"],
            "head_dim": hf["hidden_size"] // hf["num_attention_heads"]}


#: The CPU stand-in's widths: (published-key updates, trainer overrides).
STAND_IN = ({"hidden_size": 64, "intermediate_size": 128,
             "num_attention_heads": 4, "num_key_value_heads": 2,
             "vocab_size": 512},
            {"d_model": 64, "num_heads": 4, "num_kv_heads": 2,
             "head_dim": 16, "d_ff": 128, "vocab_size": 512,
             "attn_q_block": 32, "attn_kv_block": 32, "use_pallas": False})
