"""Pallas TPU flash-attention forward kernel.

Tiling: grid (batch, q_heads, nq, nk) with the kv dimension innermost and
sequential; online-softmax stats (m, l) and the output accumulator live in
VMEM scratch across kv iterations.  Each kernel's tile is chosen from the
shapes by ``flash_blocks``: up to 1024 rows a side at long sequences
(multiples of 128 on the TPU), the whole sequence at short ones.  GQA is
handled by the kv index_map (q head h reads kv head h//G), so K/V are
never replicated to the full head count in HBM.

Causal and sliding-window masking work per tile: a tile with no visible
pair skips its MXU work via ``pl.when``, and its K/V index is clamped to
the visible band (``kv_band``) so the block index repeats and no copy is
made; a tile whose every pair is visible skips the per-pair mask.  Only
the diagonal and window-edge tiles build ``pair_mask``.  Q, K and V reach
the MXU in their own dtype with float32 accumulation; the softmax
statistics, P and the accumulator are float32.

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

# Each kernel's preferred tile, (q rows, kv rows): the fastest of a sweep
# over {256, 512, 1024}^2 at B=2, S=4096, 32 q / 4 kv heads, head_dim 128,
# bf16, on one TPU v5e (PERF.md §6).  The forward went from 26.6 ms a call
# at 128 x 128 to 4.1 ms, delta, dq and dk/dv together from 43.8 to 9.3 ms.
_PREFERRED_BLOCKS = {"fwd": (1024, 1024), "dq": (1024, 1024),
                     "dkv": (512, 1024)}
_LANES = 128


def _fit_block(preferred: int, S: int) -> int:
    """The whole sequence when it fits in the preferred tile, else the
    largest of preferred, preferred/2, ..., 128 that divides it (128 when
    none does: the caller's divisibility check then names it)."""
    if S <= preferred:
        return S
    b = preferred
    while b > _LANES and S % b:
        b //= 2
    return b


def flash_blocks(Sq: int, Sk: int, kind: str) -> tuple[int, int]:
    """(q_block, kv_block) of flash kernel ``kind`` ('fwd', 'dq', 'dkv')
    from the sequence lengths alone.  Blocks clamp to the sequence and
    divide it where they can; at sequences that are multiples of 128 they
    are too."""
    qb, kb = _PREFERRED_BLOCKS[kind]
    return _fit_block(qb, Sq), _fit_block(kb, Sk)


def grid_blocks(Sq: int, Sk: int, kind: str, q_block: int | None,
                kv_block: int | None) -> tuple[int, int, int, int]:
    """The tile kernel ``kind`` runs, (q_block, kv_block, nq, nk): the
    caller's blocks, else ``flash_blocks``'s, clamped to the sequence."""
    auto_q, auto_kv = flash_blocks(Sq, Sk, kind)
    qb, kb = min(q_block or auto_q, Sq), min(kv_block or auto_kv, Sk)
    assert Sq % qb == 0 and Sk % kb == 0
    return qb, kb, Sq // qb, Sk // kb


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


def tile_full(q_start, k_start, q_block: int, kv_block: int, causal: bool,
              window: int):
    """Does every (q, k) pair in this tile pass the mask?  Such a tile
    needs no ``pair_mask``.  A full tile is visible."""
    full = True
    if causal:
        full = k_start + kv_block - 1 <= q_start
    if window > 0:
        full = jnp.logical_and(full, k_start > q_start + q_block - 1 - window)
    return full


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


def kv_band(q_start, q_block: int, kv_block: int, nk: int, causal: bool,
            window: int):
    """(first, last) kv block with a visible pair for the q tile at
    ``q_start``: kv block j is visible (``tile_visible``) iff
    first <= j <= last.  The forward and dq index maps clamp j into the
    band, so an invisible tile repeats a neighbour's block index and
    Pallas makes no copy for it."""
    first, last = 0, nk - 1
    if causal:
        last = jnp.minimum(last, (q_start + q_block - 1) // kv_block)
    if window > 0:
        first = jnp.maximum(q_start - window + 1, 0) // kv_block
    return first, last


def q_band(k_start, q_block: int, kv_block: int, nq: int, causal: bool,
           window: int):
    """(first, last) q block with a visible pair for the kv tile at
    ``k_start``: q block i is visible iff first <= i <= last.  The dk/dv
    index maps clamp i into it, as ``kv_band`` does for the forward."""
    first, last = 0, nq - 1
    if causal:
        first = k_start // q_block
    if window > 0:
        last = jnp.minimum(last, (k_start + kv_block + window - 2) // q_block)
    return first, last


def kv_index_map(G: int, q_block: int, kv_block: int, nk: int,
                 causal: bool, window: int):
    """K/V index map of the (b, h, i, j) grids of the forward and dq: kv
    head h // G, kv block j clamped into q block i's ``kv_band``."""
    def index(b, h, i, j):
        first, last = kv_band(i * q_block, q_block, kv_block, nk, causal,
                              window)
        return b, h // G, jnp.clip(j, first, last), 0
    return index


def each_visible_tile(body, q_start, k_start, q_block: int, kv_block: int,
                      causal: bool, window: int):
    """Run ``body(mask)`` on a tile with a visible pair: ``mask`` is None
    where every pair is visible, else the tile's ``pair_mask``."""
    visible = tile_visible(q_start, k_start, q_block, kv_block, causal,
                           window)
    full = tile_full(q_start, k_start, q_block, kv_block, causal, window)
    pl.when(full)(lambda: body(None))
    if full is not True:
        pl.when(jnp.logical_and(visible, jnp.logical_not(full)))(
            lambda: body(pair_mask((q_block, kv_block), q_start, k_start,
                                   causal, window)))


# contract the last dims of both operands: A @ B^T
NT = (((1,), (1,)), ((), ()))


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

    def compute(mask):
        s = lax.dot_general(q_ref[0, 0], k_ref[0, 0], NT,
                            preferred_element_type=jnp.float32) * scale
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + lax.dot_general(
            p, v_ref[0, 0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    each_visible_tile(compute, iq * q_block, ik * kv_block, q_block,
                      kv_block, causal, window)

    @pl.when(ik == nk - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)
        if with_lse:
            lse_ref[0, 0] = m_scr[...] + jnp.log(l)


def fwd_kernel_layout(qt, kt, vt, *, causal: bool = True, window: int = 0,
                      q_block: int | None = None,
                      kv_block: int | None = None,
                      with_lse: bool = False, interpret: bool = False):
    """Launch the forward in kernel layout.  qt: (B, H, Sq, D); kt, vt:
    (B, K, Sk, D).  Blocks left None come from ``flash_blocks``.  Returns
    ot, or (ot, lse) when ``with_lse``; lse is (B, H, Sq, 1) f32 — the
    trailing unit lane dim makes its blocks (q_block, 1), which Mosaic's
    (8, 128) block rule accepts."""
    B, H, Sq, D = qt.shape
    K, Sk = kt.shape[1], kt.shape[2]
    G = H // K
    q_block, kv_block, nq, nk = grid_blocks(Sq, Sk, "fwd", q_block, kv_block)
    scale = 1.0 / math.sqrt(D)

    kernel = functools.partial(
        _flash_fwd_kernel, scale=scale, causal=causal, window=window,
        q_block=q_block, kv_block=kv_block, nk=nk, with_lse=with_lse)

    kv_index = kv_index_map(G, q_block, kv_block, nk, causal, window)
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
            pl.BlockSpec((1, 1, kv_block, D), kv_index),
            pl.BlockSpec((1, 1, kv_block, D), kv_index),
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
                        q_block: int | None = None,
                        kv_block: int | None = None,
                        interpret: bool = False):
    """q: (B, Sq, H, D); k, v: (B, Sk, K, D).  Returns (B, Sq, H, D)."""
    out = fwd_kernel_layout(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=causal, window=window,
        q_block=q_block, kv_block=kv_block, interpret=interpret)
    return out.transpose(0, 2, 1, 3)
