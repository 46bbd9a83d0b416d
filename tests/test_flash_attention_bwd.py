"""Differentiable Pallas flash attention: the custom-VJP backward kernels
must match ``attention_ref``'s autodiff gradients (interpret mode on CPU),
and the SPB depth-specialized steps must show *compiled* backward elision
— strictly fewer flops AND bytes at shallow depth — via analysis/hlo.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import hlo
from repro.config import SPBConfig, TrainConfig
from repro.configs import make_batch, reduced_config
from repro.core import spb as spb_lib
from repro.kernels import ref
from repro.kernels.ops import flash_attention


def _grads(fn, q, k, v, ct):
    return jax.grad(lambda q, k, v: jnp.sum(fn(q, k, v) * ct),
                    argnums=(0, 1, 2))(q, k, v)


TOLS = {jnp.float32: dict(rtol=2e-4, atol=2e-4),
        jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


@pytest.mark.parametrize(
    "B,Sq,Sk,H,K,D,causal,window,q_block,kv_block,dtype", [
        (2, 128, 128, 4, 2, 32, True, 0, 64, 64, jnp.float32),   # GQA causal
        (1, 128, 128, 4, 4, 32, False, 0, 64, 64, jnp.float32),  # MHA bidir
        (2, 128, 128, 8, 1, 64, True, 0, 64, 64, jnp.float32),   # MQA
        (1, 256, 256, 2, 2, 64, True, 64, 64, 64, jnp.float32),  # window
        (1, 128, 256, 2, 2, 32, False, 0, 64, 64, jnp.float32),  # Sq != Sk
        # GQA with G=8 over 4 x 8 and 8 x 4 tiles: fully visible, diagonal
        # and skipped (clamped-copy) tiles in every kernel
        (1, 512, 512, 16, 2, 64, True, 0, 128, 64, jnp.float32),
        (1, 512, 512, 16, 2, 64, True, 0, 64, 128, jnp.bfloat16),
        # windows that are no multiple of the tiles
        (1, 512, 512, 2, 2, 64, True, 96, 64, 128, jnp.float32),
        (1, 512, 512, 2, 2, 64, True, 200, 128, 64, jnp.bfloat16),
        # the blocks flash_blocks picks, per kernel
        (1, 256, 256, 4, 2, 64, True, 0, None, None, jnp.float32),
    ])
def test_flash_attention_vjp_matches_ref(B, Sq, Sk, H, K, D, causal, window,
                                         q_block, kv_block, dtype):
    ks = jax.random.split(jax.random.key(0), 4)
    q = jax.random.normal(ks[0], (B, Sq, H, D), dtype)
    k = jax.random.normal(ks[1], (B, Sk, K, D), dtype)
    v = jax.random.normal(ks[2], (B, Sk, K, D), dtype)
    ct = jax.random.normal(ks[3], (B, Sq, H, D))

    def fa(q, k, v):
        return flash_attention(q, k, v, causal=causal, window=window,
                               q_block=q_block, kv_block=kv_block,
                               interpret=True)

    def fr(q, k, v):
        return ref.attention_ref(q, k, v, causal=causal, window=window)

    got = _grads(fa, q, k, v, ct)
    want = _grads(fr, q, k, v, ct)
    for g, w, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32), **TOLS[dtype],
                                   err_msg=f"d{name} mismatch")


def test_flash_attention_output_matches_vjp_forward():
    """The residual-saving forward used under jax.grad must equal the
    plain forward (same kernel math, extra lse output)."""
    ks = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(ks[0], (1, 128, 4, 32))
    k = jax.random.normal(ks[1], (1, 128, 2, 32))
    v = jax.random.normal(ks[2], (1, 128, 2, 32))

    def fa(q, k, v):
        return flash_attention(q, k, v, causal=True, q_block=64,
                               kv_block=64, interpret=True)

    out_plain = fa(q, k, v)
    out_vjp, _ = jax.vjp(fa, q, k, v)
    np.testing.assert_allclose(np.asarray(out_plain), np.asarray(out_vjp),
                               rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# Compiled backward elision (the paper's Table 1 mechanism)
# ---------------------------------------------------------------------------

def _step_cost(cfg, depth):
    from repro.dist import steps as steps_lib
    tcfg = TrainConfig(optimizer="adamw")
    step = steps_lib.make_train_step(cfg, tcfg, SPBConfig(mode="temporal"),
                                     depth=depth)
    state = steps_lib.train_state_shapes(cfg, tcfg)
    batch = {
        "tokens": jax.ShapeDtypeStruct((4, 64), jnp.int32),
        "labels": jax.ShapeDtypeStruct((4, 64), jnp.int32),
    }
    compiled = jax.jit(step).lower(state, batch).compile()
    return hlo.analyze(compiled.as_text())


def test_spb_shallow_step_has_fewer_backward_flops_and_bytes():
    """temporal SPB, k=4: the shallowest-depth jitted step must compile to
    strictly fewer flops AND HBM bytes than the full-depth step — proof
    that XLA dead-code-eliminated the prefix backward instead of merely
    scheduling it."""
    cfg = reduced_config("yi-6b")
    spb = SPBConfig(mode="temporal", k=4)
    depths = spb_lib.snapped_depths(cfg, spb)
    shallow, full = min(depths), max(depths)
    assert shallow < full

    cost_shallow = _step_cost(cfg, shallow)
    cost_full = _step_cost(cfg, full)
    assert cost_shallow.flops < cost_full.flops, (
        f"shallow {cost_shallow.flops:.3e} !< full {cost_full.flops:.3e}")
    assert cost_shallow.bytes < cost_full.bytes, (
        f"shallow {cost_shallow.bytes:.3e} !< full {cost_full.bytes:.3e}")


def test_spb_step_table_covers_schedule():
    """Every depth the temporal schedule can emit has a step-table entry —
    guards the engine's depth dispatch (missing depths would silently
    erase the SPB savings)."""
    from repro.engine import SPBEngine
    cfg = reduced_config("gemma3-4b")       # patterned: depths snap
    spb = SPBConfig(mode="temporal", k=4)
    engine = SPBEngine(cfg, TrainConfig(), spb)
    keys = set(engine.depth_keys())
    sched = spb_lib.make_schedule(cfg, spb)
    for step in range(2 * spb.k + 3):
        assert engine.depth_key_for_step(step) in keys
        assert sched.depth_at(step) in keys
