"""Pallas TPU flash-attention backward (FlashAttention-2 style).

Three kernels, all recomputing probabilities tile-by-tile in VMEM from the
forward's saved logsumexp (no S^2 materialization in HBM):

  * residual forward — the forward kernel additionally writing
    ``lse = m + log(l)`` per (batch, head, q) row;
  * preprocess — ``delta = rowsum(dO * O)`` per q row (the dV/dQ common
    subexpression of FlashAttention-2);
  * dq — grid (B, H, nq, nk), kv innermost sequential, dq accumulated in
    VMEM scratch across kv tiles;
  * dk/dv — grid (B, K, nk, G, nq): for each kv head the group's q heads
    and q tiles are innermost so dk/dv accumulate in VMEM scratch and are
    written once per kv tile (GQA sums over the q-head group without
    replicating K/V in HBM).

dq and dk/dv each take their own tile from ``flash_attention.flash_blocks``
unless the caller gives one; delta uses dq's q tile.  The masking logic is
shared with the forward kernel (``flash_attention.each_visible_tile``,
``kv_index_map`` / ``q_band``) so causal / sliding-window conventions cannot
drift between the primal and the VJP: fully masked tiles skip their MXU
work and, by the clamped index maps, their copies; only diagonal and
window-edge tiles build the per-pair mask.  Q, K, V and dO reach the MXU
in their own dtype for S = QK^T and dP = dO V^T; P, dS, the accumulators,
lse and delta are float32, so the matmuls that take P or dS take the
other operand upcast.

The ``*_kernel_layout`` entry points take/return the kernel-native
(B, H, S, D) layout — the custom VJP in ``kernels/ops.py`` saves its
residuals in that layout so the backward never re-transposes them.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention import (NT, each_visible_tile,
                                           fwd_kernel_layout, grid_blocks,
                                           kv_index_map, q_band)


# ---------------------------------------------------------------------------
# Residual forward (out + logsumexp) — the SAME kernel as the primal
# forward (flash_attention._flash_fwd_kernel), launched with with_lse=True
# ---------------------------------------------------------------------------

def fwd_res_kernel_layout(qt, kt, vt, *, causal: bool = True,
                          window: int = 0, q_block: int | None = None,
                          kv_block: int | None = None,
                          interpret: bool = False):
    """Forward in kernel layout.  qt: (B, H, Sq, D); kt, vt: (B, K, Sk, D).
    Returns (ot, lse) with ot: (B, H, Sq, D), lse: (B, H, Sq, 1) f32."""
    return fwd_kernel_layout(qt, kt, vt, causal=causal, window=window,
                             q_block=q_block, kv_block=kv_block,
                             with_lse=True, interpret=interpret)


def flash_attention_fwd_res(q, k, v, *, causal: bool = True, window: int = 0,
                            q_block: int | None = None,
                            kv_block: int | None = None,
                            interpret: bool = False):
    """Forward returning (out, lse) in the public (B, S, H, D) layout."""
    out, lse = fwd_res_kernel_layout(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=causal, window=window,
        q_block=q_block, kv_block=kv_block, interpret=interpret)
    return out.transpose(0, 2, 1, 3), lse


# ---------------------------------------------------------------------------
# Preprocess: delta = rowsum(dO * O)
# ---------------------------------------------------------------------------

def _delta_kernel(o_ref, do_ref, delta_ref):
    o = o_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    delta_ref[0, 0] = jnp.sum(o * do, axis=1, keepdims=True)


def _compute_delta(ot, dot_, q_block, interpret):
    B, H, Sq, D = ot.shape
    nq = Sq // q_block
    return pl.pallas_call(
        _delta_kernel,
        grid=(B, H, nq),
        in_specs=[
            pl.BlockSpec((1, 1, q_block, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, q_block, D), lambda b, h, i: (b, h, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, q_block, 1),
                               lambda b, h, i: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, 1), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
    )(ot, dot_)


# ---------------------------------------------------------------------------
# dq kernel: grid (B, H, nq, nk), kv innermost
# ---------------------------------------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_scr, *, scale: float, causal: bool, window: int,
               q_block: int, kv_block: int, nk: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def compute(mask):
        k = k_ref[0, 0]
        s = lax.dot_general(q_ref[0, 0], k, NT,
                            preferred_element_type=jnp.float32) * scale
        p = jnp.exp(s - lse_ref[0, 0])
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        dp = lax.dot_general(do_ref[0, 0], v_ref[0, 0], NT,
                             preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0]) * scale
        dq_scr[...] += lax.dot_general(ds, k.astype(jnp.float32),
                                       (((1,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32)

    each_visible_tile(compute, iq * q_block, ik * kv_block, q_block,
                      kv_block, causal, window)

    @pl.when(ik == nk - 1)
    def _finish():
        dq_ref[0, 0] = dq_scr[...].astype(dq_ref.dtype)


# ---------------------------------------------------------------------------
# dk/dv kernel: grid (B, K, nk, G, nq) — group heads and q tiles innermost
# ---------------------------------------------------------------------------

def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, scale: float,
                causal: bool, window: int, q_block: int, kv_block: int,
                ngroup: int, nq: int):
    jk = pl.program_id(2)
    g = pl.program_id(3)
    iq = pl.program_id(4)

    @pl.when(jnp.logical_and(g == 0, iq == 0))
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def compute(mask):
        q, do = q_ref[0, 0], do_ref[0, 0]
        s = lax.dot_general(q, k_ref[0, 0], NT,
                            preferred_element_type=jnp.float32) * scale
        p = jnp.exp(s - lse_ref[0, 0])
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        # dv += P^T dO
        dv_scr[...] += lax.dot_general(p, do.astype(jnp.float32),
                                       (((0,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32)
        dp = lax.dot_general(do, v_ref[0, 0], NT,
                             preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0]) * scale
        # dk += dS^T Q
        dk_scr[...] += lax.dot_general(ds, q.astype(jnp.float32),
                                       (((0,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32)

    each_visible_tile(compute, iq * q_block, jk * kv_block, q_block,
                      kv_block, causal, window)

    @pl.when(jnp.logical_and(g == ngroup - 1, iq == nq - 1))
    def _finish():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# Backward entries
# ---------------------------------------------------------------------------

def bwd_kernel_layout(qt, kt, vt, ot, lse, dot_, *, causal: bool = True,
                      window: int = 0, q_block: int | None = None,
                      kv_block: int | None = None, interpret: bool = False):
    """Backward in kernel layout: all operands (B, H|K, S, D), lse
    (B, H, Sq, 1) f32.  Blocks left None come from ``flash_blocks``, per
    kernel.  Returns (dqt, dkt, dvt) in the same layout."""
    B, H, Sq, D = qt.shape
    K, Sk = kt.shape[1], kt.shape[2]
    G = H // K
    scale = 1.0 / math.sqrt(D)

    qb, kb, nq, nk = grid_blocks(Sq, Sk, "dq", q_block, kv_block)
    delta = _compute_delta(ot, dot_, qb, interpret)
    kv_index = kv_index_map(G, qb, kb, nk, causal, window)

    dq_kernel = functools.partial(
        _dq_kernel, scale=scale, causal=causal, window=window,
        q_block=qb, kv_block=kb, nk=nk)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, qb, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, kb, D), kv_index),
            pl.BlockSpec((1, 1, kb, D), kv_index),
            pl.BlockSpec((1, 1, qb, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, qb, 1), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, qb, 1), lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, qb, D),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), qt.dtype),
        scratch_shapes=[pltpu.VMEM((qb, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qt, kt, vt, dot_, lse, delta)

    qb, kb, nq, nk = grid_blocks(Sq, Sk, "dkv", q_block, kv_block)

    def q_index(b, kh, j, g, i):
        first, last = q_band(j * kb, qb, kb, nq, causal, window)
        return b, kh * G + g, jnp.clip(i, first, last), 0

    dkv_kernel = functools.partial(
        _dkv_kernel, scale=scale, causal=causal, window=window,
        q_block=qb, kv_block=kb, ngroup=G, nq=nq)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(B, K, nk, G, nq),
        in_specs=[
            pl.BlockSpec((1, 1, qb, D), q_index),
            pl.BlockSpec((1, 1, kb, D), lambda b, kh, j, g, i: (b, kh, j, 0)),
            pl.BlockSpec((1, 1, kb, D), lambda b, kh, j, g, i: (b, kh, j, 0)),
            pl.BlockSpec((1, 1, qb, D), q_index),
            pl.BlockSpec((1, 1, qb, 1), q_index),
            pl.BlockSpec((1, 1, qb, 1), q_index),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, kb, D), lambda b, kh, j, g, i: (b, kh, j, 0)),
            pl.BlockSpec((1, 1, kb, D), lambda b, kh, j, g, i: (b, kh, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, K, Sk, D), kt.dtype),
            jax.ShapeDtypeStruct((B, K, Sk, D), vt.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((kb, D), jnp.float32),
            pltpu.VMEM((kb, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary", "arbitrary")),
        interpret=interpret,
    )(qt, kt, vt, dot_, lse, delta)

    return dq, dk, dv


def flash_attention_bwd(q, k, v, out, lse, do, *, causal: bool = True,
                        window: int = 0, q_block: int | None = None,
                        kv_block: int | None = None,
                        interpret: bool = False):
    """Backward in the public (B, S, H, D) layout; returns (dq, dk, dv)."""
    t = lambda x: x.transpose(0, 2, 1, 3)
    dq, dk, dv = bwd_kernel_layout(
        t(q), t(k), t(v), t(out), lse, t(do), causal=causal, window=window,
        q_block=q_block, kv_block=kv_block, interpret=interpret)
    return t(dq), t(dk), t(dv)
