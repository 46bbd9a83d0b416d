"""Pallas TPU backward kernels for the Mamba-2 SSD chunked scan.

FlashAttention-2 style split (mirrors ``flash_attention_bwd.py``):

- ``fwd_res_kernel_layout`` runs the forward kernel with ``with_states``,
  which also records the (P, N) state *entering* each chunk.  Those
  per-chunk states are the only residuals the backward needs beyond the
  inputs themselves —
  O(S/Q · P · N) extra memory instead of re-materializing the full
  sequential recurrence.
- ``bwd_kernel_layout`` walks the chunks in **reverse** grid order,
  carrying the adjoint of the inter-chunk state ``dS`` in VMEM scratch
  (seeded from the cotangent of the final state at the reverse-first
  step).  Within a chunk all gradients are (Q x Q) / (Q x N) matmuls on
  the MXU — the chunk-local recurrence reversal of the forward's masked
  decay matrix.

Forward math per chunk (state ``S_in`` entering, csum = cumsum(dA)):

    e = exp(csum);  alpha = e[-1];  d = exp(csum[-1] - csum)
    G = (c @ b^T) * L,  L[i,j] = exp(csum_i - csum_j) masked lower-tri
    y = G @ x + e[:,None] * (c @ S_in^T)
    S_out = alpha * S_in + x^T @ (b * d[:,None])

Backward per chunk, given (dy, dS_out):

    dx = G^T @ dy + d[:,None] * (b @ dS_out^T)
    dG = dy @ x^T;  M = dG * L
    dc = M @ b + e[:,None] * (dy @ S_in)
    db = M^T @ c + d[:,None] * (x @ dS_out)
    dS_in = alpha * dS_out + (dy * e[:,None])^T @ c
    dcsum = rowsum(dG*G) - colsum(dG*G)            (from L)
          + e * rowsum(dy * (c @ S_in^T))          (from e)
          - dd * d,  dd = rowsum(b * (x @ dS_out)) (from d)
    dcsum[-1] += alpha * sum(dS_out * S_in) + sum(dd * d)
    ddA = reverse-cumsum(dcsum)   (csum resets per chunk)

The cumulative sums use ``ssd.chunk_cumsum`` (Mosaic lowers no cumsum).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ssd import chunk_cumsum, ssd_fwd_kernel_layout


def fwd_res_kernel_layout(xr, dr, br, cr, *, chunk: int,
                          interpret: bool = False):
    """Forward + residuals on kernel-native layouts — the SAME kernel as
    the primal forward (``ssd._ssd_kernel``), launched with
    ``with_states=True``.

    xr: (B*H, S, P); dr: (B*H, S, 1); br, cr: (B*H, S, N).
    Returns (y (B*H,S,P) f32, state (B*H,P,N) f32,
             chunk_states (B*H, nc, P, N) f32).
    """
    return ssd_fwd_kernel_layout(xr, dr, br, cr, chunk=chunk,
                                 with_states=True, interpret=interpret)


def _bwd_kernel(xdt_ref, dA_ref, b_ref, c_ref, sin_ref, dy_ref, dstate_ref,
                dx_ref, ddA_ref, db_ref, dc_ref, ds_scr, *, chunk: int):
    """One reverse chunk step; ``ds_scr`` carries the state adjoint.
    Per-timestep vectors are (Q, 1) columns or (1, Q) rows, never 1-D."""
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _seed():
        ds_scr[...] = dstate_ref[0].astype(jnp.float32)

    x = xdt_ref[0].astype(jnp.float32)              # (Q, P)
    dA = dA_ref[0].astype(jnp.float32)              # (Q, 1)
    b = b_ref[0].astype(jnp.float32)                # (Q, N)
    c = c_ref[0].astype(jnp.float32)                # (Q, N)
    s_in = sin_ref[0, 0]                            # (P, N) f32
    dy = dy_ref[0].astype(jnp.float32)              # (Q, P)
    ds_out = ds_scr[...]                            # (P, N)

    csum, csum_row = chunk_cumsum(dA)               # (Q, 1), (1, Q)
    total = jnp.sum(dA, axis=0, keepdims=True)      # (1, 1) = csum[-1]
    e = jnp.exp(csum)                               # (Q, 1)
    alpha = jnp.exp(total)                          # (1, 1)
    d = jnp.exp(total - csum)                       # (Q, 1)
    row = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    L = jnp.where(row >= col, jnp.exp(csum - csum_row), 0.0)
    scores = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    G = scores * L                                  # (Q, Q), masked
    inter = jax.lax.dot_general(c, s_in, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (Q, P)

    # dx: intra (G^T @ dy) + state-update path
    x_ds = jax.lax.dot_general(x, ds_out, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)   # (Q, N)
    b_dsT = jax.lax.dot_general(b, ds_out, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (Q, P)
    dx = jax.lax.dot_general(G, dy, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32) + d * b_dsT

    # dG = dy @ x^T; dscores = dG * L (mask folds into L)
    dG = jax.lax.dot_general(dy, x, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)     # (Q, Q)
    M = dG * L
    dc = jax.lax.dot_general(M, b, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32) \
        + e * jax.lax.dot_general(dy, s_in, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    db = jax.lax.dot_general(M, c, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32) + d * x_ds

    # dcsum: decay-matrix term (rowsum - colsum; the colsum is a (1, Q)
    # row, turned into a column by a diagonal mask), inter-chunk e term,
    # state-update d term
    T = dG * G
    colsum = jnp.sum(jnp.where(row == col, jnp.sum(T, axis=0, keepdims=True),
                               0.0), axis=1, keepdims=True)
    dcsum = jnp.sum(T, axis=1, keepdims=True) - colsum
    dcsum = dcsum + e * jnp.sum(dy * inter, axis=1, keepdims=True)
    dd = jnp.sum(b * x_ds, axis=1, keepdims=True)   # (Q, 1)
    s_term = dd * d
    dcsum = dcsum - s_term
    last_extra = alpha * jnp.sum(jnp.sum(ds_out * s_in, axis=1, keepdims=True),
                                 axis=0, keepdims=True) \
        + jnp.sum(s_term, axis=0, keepdims=True)    # (1, 1)
    idx = lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
    dcsum = jnp.where(idx == chunk - 1, dcsum + last_extra, dcsum)
    # csum resets each chunk: ddA_t = sum_{u >= t} dcsum_u
    ddA, _ = chunk_cumsum(dcsum, reverse=True)

    # carry: adjoint of the state entering this chunk
    ds_scr[...] = alpha * ds_out + jax.lax.dot_general(
        dy * e, c, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    dx_ref[0] = dx
    ddA_ref[0] = ddA
    db_ref[0] = db
    dc_ref[0] = dc


def bwd_kernel_layout(xr, dr, br, cr, chunk_states, dy, dstate, *,
                      chunk: int, interpret: bool = False):
    """Backward on kernel-native layouts; reverse sequential chunk grid.

    Inputs as in ``fwd_res_kernel_layout`` plus the chunk-state residuals,
    the output cotangent ``dy`` (B*H, S, P) and the final-state cotangent
    ``dstate`` (B*H, P, N).  Returns (dx, ddA (B*H,S,1), db, dc), all f32.
    """
    BH, S, P = xr.shape
    N = br.shape[-1]
    assert S % chunk == 0
    nc = S // chunk

    rev = lambda b, c: (b, nc - 1 - c, 0)       # noqa: E731
    rev4 = lambda b, c: (b, nc - 1 - c, 0, 0)   # noqa: E731
    kernel = functools.partial(_bwd_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=(BH, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, P), rev),
            pl.BlockSpec((1, chunk, 1), rev),
            pl.BlockSpec((1, chunk, N), rev),
            pl.BlockSpec((1, chunk, N), rev),
            pl.BlockSpec((1, 1, P, N), rev4),
            pl.BlockSpec((1, chunk, P), rev),
            pl.BlockSpec((1, P, N), lambda b, c: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, P), rev),
            pl.BlockSpec((1, chunk, 1), rev),
            pl.BlockSpec((1, chunk, N), rev),
            pl.BlockSpec((1, chunk, N), rev),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, P), jnp.float32),
            jax.ShapeDtypeStruct((BH, S, 1), jnp.float32),
            jax.ShapeDtypeStruct((BH, S, N), jnp.float32),
            jax.ShapeDtypeStruct((BH, S, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(xr, dr, br, cr, chunk_states, dy, dstate)
