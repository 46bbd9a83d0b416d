"""Optimizers, gradient-compression baselines, data pipeline."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.config import TrainConfig
from repro.core import compress
from repro.data.pipeline import MarkovLM, Pipeline, classification_task
from repro.optim import optimizers as opt


# ---------------------------------------------------------------- optimizers

def _quadratic_setup(optname, dtype=jnp.float32):
    params = {"w": jnp.full((8,), 5.0, dtype)}
    tcfg = TrainConfig(optimizer=optname, learning_rate=0.3,
                       weight_decay=0.0, grad_clip=0.0, num_steps=200,
                       warmup_steps=1)
    state = opt.init_opt_state(params, tcfg)
    return params, state, tcfg


@pytest.mark.parametrize("optname", ["adamw", "sgdm"])
def test_optimizer_converges_quadratic(optname):
    params, state, tcfg = _quadratic_setup(optname)
    for step in range(150):
        grads = {"w": params["w"].astype(jnp.float32)}     # d/dw (w^2/2)
        params, state, m = opt.apply_updates(
            params, grads, state, jnp.asarray(step), tcfg)
    assert float(jnp.abs(params["w"]).max()) < 0.3


def test_mixed_precision_master_weights():
    params, state, tcfg = _quadratic_setup("adamw", jnp.bfloat16)
    assert "master" in state
    for step in range(20):
        grads = {"w": params["w"].astype(jnp.float32)}
        params, state, _ = opt.apply_updates(
            params, grads, state, jnp.asarray(step), tcfg)
    assert params["w"].dtype == jnp.bfloat16
    assert state["master"]["w"].dtype == jnp.float32
    # master tracks higher precision than bf16 params
    np.testing.assert_allclose(np.asarray(state["master"]["w"]),
                               np.asarray(params["w"], np.float32),
                               atol=0.05)


def test_grad_clip():
    params = {"w": jnp.zeros((4,))}
    tcfg = TrainConfig(optimizer="sgdm", grad_clip=1.0, learning_rate=1.0,
                       weight_decay=0.0, momentum=0.0, warmup_steps=1)
    state = opt.init_opt_state(params, tcfg)
    grads = {"w": jnp.full((4,), 100.0)}
    new_params, _, m = opt.apply_updates(params, grads, state,
                                         jnp.asarray(0), tcfg)
    assert float(m["grad_norm"]) == pytest.approx(200.0)
    # update magnitude bounded by lr * clip
    assert float(jnp.linalg.norm(new_params["w"])) <= 1.01


def test_lr_schedule_warmup_and_decay():
    tcfg = TrainConfig(learning_rate=1e-2, warmup_steps=10, num_steps=100)
    lrs = [float(opt.lr_at(tcfg, jnp.asarray(s))) for s in range(100)]
    assert lrs[0] < lrs[9] <= 1e-2 * 1.001
    assert lrs[99] < lrs[20]


# ---------------------------------------------------------------- compression

@given(ratio=st.floats(0.05, 0.9))
@settings(max_examples=10, deadline=None)
def test_topk_keeps_largest(ratio):
    g = jax.random.normal(jax.random.key(0), (64, 32))
    out = compress.topk_apply(g, ratio)
    kept = np.asarray(out) != 0
    k = max(1, int(g.size * ratio))
    assert kept.sum() == k
    thresh = np.sort(np.abs(np.asarray(g)).ravel())[-k]
    assert np.all(np.abs(np.asarray(g))[kept] >= thresh - 1e-7)


def test_compress_tree_roundtrip_none():
    g = {"a": jnp.ones((4, 4)), "b": [jnp.zeros((2,))]}
    out = compress.compress_tree(g, "none", 0.1, jax.random.key(0))
    assert jax.tree.structure(out) == jax.tree.structure(g)


def test_lowrank_reduces_error_with_rank():
    g = jax.random.normal(jax.random.key(1), (32, 32))
    e = []
    for r in (1, 8, 32):
        approx = compress.lowrank_apply(g, r, jax.random.key(2))
        e.append(float(jnp.linalg.norm(approx - g)))
    assert e[0] > e[1] > e[2]
    assert e[2] < 1e-3                       # full rank ~ exact


# ---------------------------------------------------------------- data

def test_pipeline_deterministic():
    from repro.configs import reduced_config
    cfg = reduced_config("yi-6b")
    p1 = Pipeline(cfg, 4, 32, seed=7)
    p2 = Pipeline(cfg, 4, 32, seed=7)
    b1, b2 = p1.get_batch(3), p2.get_batch(3)
    np.testing.assert_array_equal(np.asarray(b1["tokens"]),
                                  np.asarray(b2["tokens"]))
    # different steps/shard differ
    b3 = p1.get_batch(4)
    assert not np.array_equal(np.asarray(b1["tokens"]), np.asarray(b3["tokens"]))
    p3 = Pipeline(cfg, 4, 32, seed=7, shard=1, num_shards=2)
    b4 = p3.get_batch(3)
    assert not np.array_equal(np.asarray(b1["tokens"]), np.asarray(b4["tokens"]))


def test_markov_is_learnable_structure():
    """Bigram stream has much lower conditional entropy than uniform."""
    lm = MarkovLM(64, seed=0)
    toks = lm.sample(8, 512, step=0)
    # empirical conditional entropy under the true transition matrix
    probs = lm.transition_prob(toks[:, :-1], toks[:, 1:])
    ce = -np.log(probs + 1e-9).mean()
    assert ce < np.log(64) * 0.9


def test_classification_task_separable():
    x, y = classification_task(512, 16, 4, seed=0)
    assert x.shape == (512, 16) and set(np.asarray(y)) <= set(range(4))
