"""Plain float32 reference of the benchmark's training cells.

Straightforward ``jax.numpy`` at ``Precision.HIGHEST``, written from the
published descriptions and the configuration file's keys alone; it
imports nothing of the program.  It follows the first steps of a cell's
training job: the same seeded weights (``weights.py``), the same batches,
the same SPB depth at each step, gradient clipping, SPB's per-block
rescale and AdamW.

Models (``model_type`` of the configuration file):

* ``llama`` (Yi): pre-norm decoder, RMSNorm, rotary embedding on the
  rotate-half convention with the file's ``rope_theta``, grouped-query
  causal attention, SwiGLU MLP.
* ``mamba2``: pre-norm Mamba-2 blocks (in-projection to z, x, B, C, dt;
  causal depthwise conv with bias and SiLU over x, B, C; softplus dt with
  bias; A = -exp(A_log); SSD; skip D; gated RMSNorm of y * silu(z);
  out-projection).  The SSD is the chunked "minimal" algorithm of the
  Mamba-2 paper (arXiv:2405.21060, listing 1), in float32.

Both: the configuration's ``assumed`` departures (input embedding scaled
by sqrt(hidden_size); output head tied to the input embedding), the
cross-entropy over the first ``vocab_size`` rows of the padded table.

``low`` (the control) rounds every matmul operand to a lower precision
(float8 e4m3, scaled per tensor) and keeps float32 accumulation;
``half_batch`` takes the loss over the first half of the rows only (a
planted fault).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
HI = lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class Variant:
    """How the reference is computed: ``low`` is None (float32) or a
    lower dtype name for matmul operands; ``half_batch`` plants a fault."""
    low: Optional[str] = None
    half_batch: bool = False


def _cast(t, low):
    """``t`` in float32, or rounded to ``low`` as a scaled float8 matmul
    would take it: scaled per tensor so that its largest magnitude meets
    the format's largest finite value, rounded, scaled back.  The rounding
    passes the gradient straight through, so the backward's cotangents
    stay in float32 and only its saved operands carry the rounding."""
    t = t.astype(F32)
    if low is None:
        return t
    top = float(jnp.finfo(low).max)
    scale = lax.stop_gradient(top / jnp.maximum(jnp.max(jnp.abs(t)), 1e-30))
    r = (t * scale).astype(low).astype(F32) / scale
    return t + lax.stop_gradient(r - t)


def ein(spec, *ops, low=None):
    return jnp.einsum(spec, *[_cast(o, low) for o in ops], precision=HI,
                      preferred_element_type=F32)


def rms(x, w, eps):
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(ms + eps) * (1.0 + w.astype(F32))


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


def llama_layer(p, x, hf, low):
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


def mamba2_layer(p, x, hf, low):
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


LAYERS = {"llama": llama_layer, "mamba2": mamba2_layer}


def loss(params, tokens, labels, hf, depth, variant: Variant,
         rows_per_block=1024):
    """Mean next-token cross-entropy; layers below ``depth`` from the top
    run without gradient (the SPB truncation).  Each layer runs one batch
    row at a time and the head one block of rows at a time (scans under
    rematerialization), so the reference fits one chip beside its
    optimizer state."""
    low = variant.low
    if variant.half_batch:
        half = tokens.shape[0] // 2
        tokens, labels = tokens[:half], labels[:half]
    n_layers = hf["num_hidden_layers"]
    vocab, d = hf["vocab_size"], hf["hidden_size"]
    boundary = n_layers - depth
    tok = params["embed"]["tok"].astype(F32)
    x = tok[tokens] * math.sqrt(d)
    layer = jax.checkpoint(functools.partial(LAYERS[hf["model_type"]],
                                             hf=hf, low=low))

    def body(x, lp):
        l, p = lp
        frozen = l < boundary      # no gradient reaches a frozen layer,
        #                            nor the embedding below it
        p = jax.tree.map(lambda t: jnp.where(frozen, lax.stop_gradient(t), t),
                         p)
        x = jnp.where(frozen, lax.stop_gradient(x), x)
        return lax.map(lambda row: layer(p, row[None])[0], x), None

    x = lax.scan(body, x, (jnp.arange(n_layers), params["groups"][0][0]))[0]
    x = rms(x, params["final_norm"], hf["rms_norm_eps"])
    rows = math.gcd(rows_per_block, labels.size)
    x = x.reshape(-1, rows, d)
    y = labels.reshape(-1, rows)
    head = tok[:vocab]

    @jax.checkpoint
    def block(xb, yb):
        logits = ein("td,vd->tv", xb, head, low=low)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, yb[:, None], axis=-1)[:, 0]
        return jnp.sum(lse - gold)

    return jnp.sum(lax.map(lambda a: block(*a), (x, y))) / labels.size


# -- the optimizer the job states ---------------------------------------------

def contributors(n_layers: int, depths: Sequence[int]) -> Tuple[int, ...]:
    """How many of the cycle's depths train each layer (layer 0 = input)."""
    return tuple(sum(1 for d in depths if l >= n_layers - d)
                 for l in range(n_layers))


def lr_at(opt: Dict[str, Any], step):
    """Linear warm-up, then cosine decay to 10% over ``num_steps``."""
    step = jnp.asarray(step, F32)
    warm = jnp.minimum(1.0, (step + 1) / max(opt["warmup_steps"], 1))
    frac = jnp.clip(step / max(opt["num_steps"], 1), 0.0, 1.0)
    return opt["learning_rate"] * warm * (0.55 + 0.45 * jnp.cos(jnp.pi * frac))


def gradient(params, tokens, labels, *, hf, job, depth, variant: Variant):
    """Loss and gradient at ``depth``, clipped by the global norm and, under
    SPB, rescaled per layer by k over the layer's contributors (the
    weighted average of the paper, as the trainer applies it)."""
    opt = job["optimizer"]
    value, grads = jax.value_and_grad(loss)(params, tokens, labels, hf,
                                            depth, variant)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                         for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gnorm, 1e-9))
    grads = jax.tree.map(lambda g: g * scale, grads)
    spb = job["spb"]
    if spb["mode"] != "off" and spb.get("lr_rescale", True):
        n_layers = hf["num_hidden_layers"]
        c = contributors(n_layers, job["depths"])
        s = jnp.asarray([spb["k"] / ci if ci else 0.0 for ci in c], F32)
        grads["groups"][0][0] = jax.tree.map(
            lambda g: g * s.reshape((-1,) + (1,) * (g.ndim - 1)),
            grads["groups"][0][0])
    return value, grads


def adamw(params, moments, grads, step, *, job):
    """AdamW with bias correction and decoupled weight decay, at the job's
    learning-rate schedule."""
    opt = job["optimizer"]
    lr = lr_at(opt, step)
    b1, b2, eps, wd = (opt["beta1"], opt["beta2"], opt["eps"],
                       opt["weight_decay"])
    t = jnp.asarray(step, F32) + 1.0
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, moments["mu"], grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, moments["nu"],
                      grads)
    new = jax.tree.map(
        lambda p, m, v: p - lr * ((m / (1 - b1 ** t))
                                  / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
                                  + wd * p),
        params, mu, nu)
    return new, {"mu": mu, "nu": nu}
