"""Plain float32 reference of the benchmark's training cells.

Straightforward ``jax.numpy`` at ``Precision.HIGHEST``, written from the
published descriptions and the configuration file's keys alone; it
imports nothing of the program.  It follows the first steps of a cell's
training job: the same seeded weights (``weights.py``), the same batches,
the same SPB depth at each step, gradient clipping, SPB's per-block
rescale and AdamW.

The layers are the architecture's (``perfbench/archs/<model_type>.py``,
found by ``registry.arch``): its ``layer``, run by :func:`scan_layers`
or by a ``stack`` of its own.  Shared by all: the configuration's
``assumed`` departures (input embedding scaled by sqrt(hidden_size);
output head tied to the input embedding), the final RMSNorm, the
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

from perfbench import registry

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


def scan_layers(layer, params, x, hf, low, boundary):
    """The default stack: the layers of one group
    (``params["groups"][0][0]``, stacked on a leading axis) in a scan, each
    under rematerialization and one batch row at a time; the layers below
    ``boundary`` run without gradient (the SPB truncation)."""
    n_layers = hf["num_hidden_layers"]
    layer = jax.checkpoint(functools.partial(layer, hf=hf, low=low))

    def body(x, lp):
        l, p = lp
        frozen = l < boundary      # no gradient reaches a frozen layer,
        #                            nor the embedding below it
        p = jax.tree.map(lambda t: jnp.where(frozen, lax.stop_gradient(t), t),
                         p)
        x = jnp.where(frozen, lax.stop_gradient(x), x)
        return lax.map(lambda row: layer(p, row[None])[0], x), None

    return lax.scan(body, x, (jnp.arange(n_layers),
                              params["groups"][0][0]))[0]


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
    vocab, d = hf["vocab_size"], hf["hidden_size"]
    boundary = hf["num_hidden_layers"] - depth
    tok = params["embed"]["tok"].astype(F32)
    x = tok[tokens] * math.sqrt(d)
    arch = registry.arch(hf["model_type"])
    stack = getattr(arch, "stack", functools.partial(scan_layers, arch.layer))
    x = stack(params, x, hf, low, boundary)
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
