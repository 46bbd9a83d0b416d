"""Pallas TPU kernel for the Mamba-2 SSD (state-space duality) scan.

Chunked form (Dao & Gu, arXiv:2405.21060): within a chunk of Q timesteps
the recurrence is computed as a masked (Q x Q) matmul (MXU work); across
chunks a (P x N) state is carried in VMEM scratch along the sequential
grid dimension.  Grid: (batch*heads, n_chunks); per-step blocks are
(Q, P) inputs and (Q, N) B/C projections — VMEM-resident, MXU-aligned for
P, N multiples of 128 at full scale.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def chunk_cumsum(col, *, reverse: bool = False):
    """Inclusive prefix sums (suffix sums with ``reverse``) of a (Q, 1)
    column, returned both as a (Q, 1) column and as a (1, Q) row.

    Mosaic lowers no ``cumsum``.  The column is broadcast across lanes, a
    triangular iota mask is summed over sublanes to give the row, and a
    diagonal mask summed over lanes turns that row back into a column —
    O(Q^2) VPU work, the same order as the decay matrix built next to it.
    """
    q = col.shape[0]
    row = lax.broadcasted_iota(jnp.int32, (q, q), 0)
    lane = lax.broadcasted_iota(jnp.int32, (q, q), 1)
    keep = row >= lane if reverse else row <= lane
    srow = jnp.sum(jnp.where(keep, col, 0.0), axis=0, keepdims=True)
    scol = jnp.sum(jnp.where(row == lane, srow, 0.0), axis=1, keepdims=True)
    return scol, srow


def _ssd_kernel(xdt_ref, dA_ref, b_ref, c_ref, y_ref, state_out_ref, *rest,
                chunk: int, nc: int, with_states: bool):
    if with_states:
        chunk_states_ref, state_scr = rest
    else:
        (state_scr,) = rest
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    if with_states:
        # residual for the backward: the (P, N) state *entering* this chunk
        chunk_states_ref[0, 0] = state_scr[...]

    xdt = xdt_ref[0].astype(jnp.float32)            # (Q, P)
    dA = dA_ref[0].astype(jnp.float32)              # (Q, 1)
    b = b_ref[0].astype(jnp.float32)                # (Q, N)
    c = c_ref[0].astype(jnp.float32)                # (Q, N)

    csum, csum_row = chunk_cumsum(dA)               # (Q, 1), (1, Q)
    total = jnp.sum(dA, axis=0, keepdims=True)      # (1, 1) = csum[-1]
    # intra-chunk decay matrix L[i,j] = exp(csum_i - csum_j), lower-tri
    row = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    L = jnp.where(row >= col, jnp.exp(csum - csum_row), 0.0)
    scores = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # (Q,Q)
    y = jax.lax.dot_general(scores * L, xdt, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)       # (Q,P)
    # inter-chunk: y += exp(csum) * (C @ state^T)
    state = state_scr[...]                          # (P, N)
    y = y + jnp.exp(csum) * jax.lax.dot_general(
        c, state, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    y_ref[0] = y.astype(y_ref.dtype)
    # state update: state' = state * exp(total) + xdt^T @ (B * decay)
    decay = jnp.exp(total - csum)                   # (Q, 1)
    upd = jax.lax.dot_general(xdt, b * decay,
                              (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)     # (P,N)
    state_scr[...] = state * jnp.exp(total) + upd

    @pl.when(ic == nc - 1)
    def _emit_state():
        state_out_ref[0] = state_scr[...]


def ssd_fwd_kernel_layout(xr, dr, br, cr, *, chunk: int,
                          with_states: bool = False,
                          interpret: bool = False):
    """Forward scan on kernel-native layouts.

    xr: (B*H, S, P); dr: (B*H, S, 1); br, cr: (B*H, S, N).
    Returns (y: (B*H, S, P) f32, final_state: (B*H, P, N) f32), plus the
    per-chunk entering states (B*H, nc, P, N) f32 when ``with_states``
    (the residuals of the backward in ``ssd_bwd.py``).
    """
    BH, S, P = xr.shape
    N = br.shape[-1]
    assert S % chunk == 0
    nc = S // chunk

    kernel = functools.partial(_ssd_kernel, chunk=chunk, nc=nc,
                               with_states=with_states)
    out_specs = [
        pl.BlockSpec((1, chunk, P), lambda b, c: (b, c, 0)),
        pl.BlockSpec((1, P, N), lambda b, c: (b, 0, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((BH, S, P), jnp.float32),
        jax.ShapeDtypeStruct((BH, P, N), jnp.float32),
    ]
    if with_states:
        out_specs.append(pl.BlockSpec((1, 1, P, N),
                                      lambda b, c: (b, c, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((BH, nc, P, N), jnp.float32))
    return tuple(pl.pallas_call(
        kernel,
        grid=(BH, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, P), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, 1), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(xr, dr, br, cr))


def ssd_scan(xdt, dA, B_, C, *, chunk: int = 128, interpret: bool = False):
    """Chunked SSD scan.

    xdt: (B, S, H, P) f32-ish (inputs pre-multiplied by dt)
    dA:  (B, S, H)
    B_, C: (B, S, H, N) (already broadcast over groups)
    Returns (y: (B, S, H, P) f32, final_state: (B, H, P, N) f32).
    """
    Bb, S, H, P = xdt.shape
    N = B_.shape[-1]
    chunk = min(chunk, S)
    assert S % chunk == 0
    BH = Bb * H

    # (B*H, S, ...) layouts
    xr = xdt.transpose(0, 2, 1, 3).reshape(BH, S, P)
    dr = dA.transpose(0, 2, 1).reshape(BH, S, 1)
    br = B_.transpose(0, 2, 1, 3).reshape(BH, S, N)
    cr = C.transpose(0, 2, 1, 3).reshape(BH, S, N)

    y, state = ssd_fwd_kernel_layout(xr, dr, br, cr, chunk=chunk,
                                     interpret=interpret)
    y = y.reshape(Bb, H, S, P).transpose(0, 2, 1, 3)
    state = state.reshape(Bb, H, P, N)
    return y, state
