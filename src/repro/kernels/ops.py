"""Public jit'd wrappers for the Pallas kernels.

``interpret`` resolution (per call site, satellite of PR 10): every public
op takes ``interpret=None`` and resolves it **before** the jit boundary —
an explicit argument wins, then a ``force_interpret(...)`` context, then
the backend default (interpret everywhere but TPU).  The resolved flag is
a static jit argument, so flipping the context or backend retraces
instead of silently reusing a stale cache entry, and the same flag is
threaded through each ``custom_vjp`` as a nondiff argument — forward and
backward kernels always run in the same mode.

All three ops are differentiable: flash attention via the
FlashAttention-2 backward kernels (``flash_attention_bwd.py``), the SSD
scan and the RG-LRU scan via chunk-local recurrence reversal with carried
adjoint state (``ssd_bwd.py`` / ``rglru_bwd.py``).  The pure-jnp
blockwise implementations in ``repro.models`` remain the default model
path on CPU so that dry-run lowering stays GSPMD-shardable; models opt
into the kernels with ``ModelConfig.use_pallas``.
"""
from __future__ import annotations

import contextlib
import contextvars
import functools

import jax
import jax.numpy as jnp

from repro.kernels import rglru_bwd as _rglru_bwd_mod
from repro.kernels import ssd_bwd as _ssd_bwd_mod
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.flash_attention_bwd import (bwd_kernel_layout,
                                               fwd_res_kernel_layout)
from repro.kernels.rglru import rglru_scan
from repro.kernels.ssd import ssd_fwd_kernel_layout


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


_INTERPRET: contextvars.ContextVar[bool | None] = contextvars.ContextVar(
    "pallas_interpret", default=None)


def resolve_interpret(interpret: bool | None = None) -> bool:
    """Resolve an ``interpret`` request to a concrete bool.

    Precedence: explicit argument > ``force_interpret`` context > backend
    default (interpret mode everywhere except TPU).
    """
    if interpret is not None:
        return bool(interpret)
    forced = _INTERPRET.get()
    if forced is not None:
        return bool(forced)
    return not _on_tpu()


@contextlib.contextmanager
def force_interpret(value: bool):
    """Force ``interpret`` for every kernel call in the dynamic scope."""
    token = _INTERPRET.set(bool(value))
    try:
        yield
    finally:
        _INTERPRET.reset(token)


# ---------------------------------------------------------------------------
# Flash attention
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_attention(q, k, v, causal, window, q_block, kv_block, interpret):
    return flash_attention_fwd(q, k, v, causal=causal, window=window,
                               q_block=q_block, kv_block=kv_block,
                               interpret=interpret)


def _t(x):
    return x.transpose(0, 2, 1, 3)      # (B,S,H,D) <-> (B,H,S,D)


def _flash_attention_fwd(q, k, v, causal, window, q_block, kv_block,
                         interpret):
    # residuals are kept in the kernel-native (B,H,S,D) layout so the
    # backward launches straight into its kernels without re-transposing
    qt, kt, vt = _t(q), _t(k), _t(v)
    ot, lse = fwd_res_kernel_layout(
        qt, kt, vt, causal=causal, window=window, q_block=q_block,
        kv_block=kv_block, interpret=interpret)
    return _t(ot), (qt, kt, vt, ot, lse)


def _flash_attention_bwd(causal, window, q_block, kv_block, interpret,
                         res, g):
    qt, kt, vt, ot, lse = res
    dq, dk, dv = bwd_kernel_layout(
        qt, kt, vt, ot, lse, _t(g), causal=causal, window=window,
        q_block=q_block, kv_block=kv_block, interpret=interpret)
    return _t(dq), _t(dk), _t(dv)


_flash_attention.defvjp(_flash_attention_fwd, _flash_attention_bwd)


@functools.partial(jax.jit, static_argnames=("causal", "window", "q_block",
                                             "kv_block", "interpret"))
def _flash_attention_jit(q, k, v, causal, window, q_block, kv_block,
                         interpret):
    return _flash_attention(q, k, v, causal, window, q_block, kv_block,
                            interpret)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    q_block: int | None = None, kv_block: int | None = None,
                    interpret: bool | None = None):
    """Differentiable flash attention (custom VJP: FlashAttention-2
    backward kernels — see ``kernels/flash_attention_bwd.py``).

    q: (B, Sq, H, D); k, v: (B, Sk, K, D).  With the blocks left None each
    kernel (forward, dq, dk/dv) tiles by ``flash_attention.flash_blocks``
    from the shapes; blocks given here apply to every kernel."""
    return _flash_attention_jit(q, k, v, causal, window, q_block, kv_block,
                                resolve_interpret(interpret))


# ---------------------------------------------------------------------------
# SSD (Mamba-2) chunked scan
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _ssd(xr, dr, br, cr, chunk, interpret):
    return ssd_fwd_kernel_layout(xr, dr, br, cr, chunk=chunk,
                                 interpret=interpret)


def _ssd_fwd(xr, dr, br, cr, chunk, interpret):
    y, state, chunk_states = _ssd_bwd_mod.fwd_res_kernel_layout(
        xr, dr, br, cr, chunk=chunk, interpret=interpret)
    return (y, state), (xr, dr, br, cr, chunk_states)


def _ssd_bwd(chunk, interpret, res, ct):
    xr, dr, br, cr, chunk_states = res
    dy, dstate = ct
    dx, ddA, db, dc = _ssd_bwd_mod.bwd_kernel_layout(
        xr, dr, br, cr, chunk_states, dy.astype(jnp.float32),
        dstate.astype(jnp.float32), chunk=chunk, interpret=interpret)
    return (dx.astype(xr.dtype), ddA.astype(dr.dtype),
            db.astype(br.dtype), dc.astype(cr.dtype))


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _ssd_jit(xdt, dA, B_, C, chunk, interpret):
    Bb, S, H, P = xdt.shape
    N = B_.shape[-1]
    Q = min(chunk, S)
    pad = (-S) % Q
    if pad:
        # zero inputs + zero log-decay (exp(0)=1) carry the state through
        # the tail unchanged — same convention as models/ssm.py::_ssd_scan
        zpad = ((0, 0), (0, pad), (0, 0), (0, 0))
        xdt = jnp.pad(xdt, zpad)
        dA = jnp.pad(dA, ((0, 0), (0, pad), (0, 0)))
        B_ = jnp.pad(B_, zpad)
        C = jnp.pad(C, zpad)
    Sp = S + pad
    BH = Bb * H
    xr = xdt.transpose(0, 2, 1, 3).reshape(BH, Sp, P)
    dr = dA.transpose(0, 2, 1).reshape(BH, Sp, 1)
    br = B_.transpose(0, 2, 1, 3).reshape(BH, Sp, N)
    cr = C.transpose(0, 2, 1, 3).reshape(BH, Sp, N)
    y, state = _ssd(xr, dr, br, cr, Q, interpret)
    y = y.reshape(Bb, H, Sp, P).transpose(0, 2, 1, 3)[:, :S]
    return y, state.reshape(Bb, H, P, N)


def ssd(xdt, dA, B_, C, *, chunk: int = 128, interpret: bool | None = None):
    """Differentiable chunked SSD scan (custom VJP: reverse-chunk
    recurrence reversal — see ``kernels/ssd_bwd.py``).

    xdt: (B, S, H, P); dA: (B, S, H); B_, C: (B, S, H, N).  Non-divisible
    sequence lengths are zero-padded to a whole chunk (autodiff flows
    through the pad/slice, outside the custom VJP).
    Returns (y: (B, S, H, P) f32, final_state: (B, H, P, N) f32).
    """
    return _ssd_jit(xdt, dA, B_, C, chunk, resolve_interpret(interpret))


# ---------------------------------------------------------------------------
# RG-LRU (Griffin) linear recurrence
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _rglru(a, b, chunk, width_block, interpret):
    return rglru_scan(a, b, chunk=chunk, width_block=width_block,
                      interpret=interpret)


def _rglru_fwd(a, b, chunk, width_block, interpret):
    y = rglru_scan(a, b, chunk=chunk, width_block=width_block,
                   interpret=interpret)
    return y, (a, y)


def _rglru_bwd(chunk, width_block, interpret, res, dy):
    a, y = res
    # h_{t-1}: the forward output shifted right by one step (h_{-1} = 0)
    y_prev = jnp.pad(y, ((0, 0), (1, 0), (0, 0)))[:, :-1]
    da, db = _rglru_bwd_mod.bwd_kernel_layout(
        a, y_prev, dy.astype(jnp.float32), chunk=chunk,
        width_block=width_block, interpret=interpret)
    return da.astype(a.dtype), db.astype(a.dtype)


_rglru.defvjp(_rglru_fwd, _rglru_bwd)


@functools.partial(jax.jit, static_argnames=("chunk", "width_block",
                                             "interpret"))
def _rglru_jit(a, b, chunk, width_block, interpret):
    B, S, W = a.shape
    Q = min(chunk, S)
    pad = (-S) % Q
    if pad:
        # a=1, b=0 on the tail holds the state — same convention as
        # models/ssm.py::_lru_scan
        a = jnp.pad(a, ((0, 0), (0, pad), (0, 0)), constant_values=1.0)
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0)))
    y = _rglru(a, b, Q, width_block, interpret)
    return y[:, :S]


def rglru(a, b, *, chunk: int = 128, width_block: int = 128,
          interpret: bool | None = None):
    """Differentiable RG-LRU scan (custom VJP: the reverse linear
    recurrence — see ``kernels/rglru_bwd.py``).

    a, b: (B, S, W).  Non-divisible sequence lengths are padded with
    (a=1, b=0), which carries the state through the tail unchanged.
    Returns h: (B, S, W) f32.
    """
    return _rglru_jit(a, b, chunk, width_block, resolve_interpret(interpret))
