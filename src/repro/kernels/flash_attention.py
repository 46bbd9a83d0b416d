"""Pallas TPU flash-attention forward kernel.

Tiling: grid (batch, q_heads, nq, nk) with the kv dimension innermost and
sequential; online-softmax stats (m, l) and the output accumulator live in
VMEM scratch across kv iterations.  Block shapes are MXU-aligned
(q_block x head_dim and kv_block x head_dim, multiples of 128 at full
scale).  GQA is handled by the kv index_map (q head h reads kv head h//G),
so K/V are never replicated to the full head count in HBM.

Causal and sliding-window masking skip fully-masked kv blocks via
``pl.when`` — on TPU the MXU work for out-of-window blocks is elided.

The single kernel is parameterized on ``with_lse``: the plain forward
drops the logsumexp; the differentiable path (``flash_attention_bwd``)
launches the same kernel with ``with_lse=True`` so the primal and the
VJP forward can never drift numerically.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def tile_visible(q_start, k_start, q_block: int, kv_block: int,
                 causal: bool, window: int):
    """Does any (q, k) pair in this tile pass the causal/window mask?
    Shared by the forward and backward kernels so the skip condition can
    never drift from the per-pair mask below."""
    visible = True
    if causal:
        visible = k_start <= q_start + q_block - 1
    if window > 0:
        visible = jnp.logical_and(
            visible, k_start + kv_block - 1 > q_start - window)
    return visible


def pair_mask(s_shape, q_start, k_start, causal: bool, window: int):
    """Per-(q, k) visibility mask for one score tile."""
    qpos = q_start + lax.broadcasted_iota(jnp.int32, s_shape, 0)
    kpos = k_start + lax.broadcasted_iota(jnp.int32, s_shape, 1)
    mask = jnp.ones(s_shape, jnp.bool_)
    if causal:
        mask = jnp.logical_and(mask, kpos <= qpos)
    if window > 0:
        mask = jnp.logical_and(mask, kpos > qpos - window)
    return mask


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, *rest, scale: float,
                      causal: bool, window: int, q_block: int,
                      kv_block: int, nk: int, with_lse: bool):
    if with_lse:
        lse_ref, m_scr, l_scr, acc_scr = rest
    else:
        m_scr, l_scr, acc_scr = rest
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = iq * q_block
    k_start = ik * kv_block

    @pl.when(tile_visible(q_start, k_start, q_block, kv_block, causal,
                          window))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)           # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)           # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)           # (bk, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        mask = pair_mask(s.shape, q_start, k_start, causal, window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)
        if with_lse:
            lse_ref[0, 0] = m_scr[...] + jnp.log(l)


def fwd_kernel_layout(qt, kt, vt, *, causal: bool = True, window: int = 0,
                      q_block: int = 128, kv_block: int = 128,
                      with_lse: bool = False, interpret: bool = False):
    """Launch the forward in kernel layout.  qt: (B, H, Sq, D); kt, vt:
    (B, K, Sk, D).  Returns ot, or (ot, lse) when ``with_lse``; lse is
    (B, H, Sq, 1) f32 — the trailing unit lane dim makes its blocks
    (q_block, 1), which Mosaic's (8, 128) block rule accepts."""
    B, H, Sq, D = qt.shape
    K, Sk = kt.shape[1], kt.shape[2]
    G = H // K
    q_block = min(q_block, Sq)
    kv_block = min(kv_block, Sk)
    assert Sq % q_block == 0 and Sk % kv_block == 0
    nq, nk = Sq // q_block, Sk // kv_block
    scale = 1.0 / math.sqrt(D)

    kernel = functools.partial(
        _flash_fwd_kernel, scale=scale, causal=causal, window=window,
        q_block=q_block, kv_block=kv_block, nk=nk, with_lse=with_lse)

    out_specs = [pl.BlockSpec((1, 1, q_block, D),
                              lambda b, h, i, j: (b, h, i, 0))]
    out_shape = [jax.ShapeDtypeStruct((B, H, Sq, D), qt.dtype)]
    if with_lse:
        out_specs.append(pl.BlockSpec((1, 1, q_block, 1),
                                      lambda b, h, i, j: (b, h, i, 0)))
        out_shape.append(jax.ShapeDtypeStruct((B, H, Sq, 1), jnp.float32))

    result = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, q_block, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, kv_block, D), lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, kv_block, D), lambda b, h, i, j: (b, h // G, j, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((q_block, 1), jnp.float32),
            pltpu.VMEM((q_block, 1), jnp.float32),
            pltpu.VMEM((q_block, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qt, kt, vt)
    if with_lse:
        return result[0], result[1]
    return result[0]


def flash_attention_fwd(q, k, v, *, causal: bool = True, window: int = 0,
                        q_block: int = 128, kv_block: int = 128,
                        interpret: bool = False):
    """q: (B, Sq, H, D); k, v: (B, Sk, K, D).  Returns (B, Sq, H, D)."""
    out = fwd_kernel_layout(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=causal, window=window,
        q_block=q_block, kv_block=kv_block, interpret=interpret)
    return out.transpose(0, 2, 1, 3)
