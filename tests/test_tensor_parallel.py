"""True 3-D parallelism: tensor-sharded pipeline stages with explicit
collectives and ZeRO-2 gradient sharding.

Fast tier: tensor-parallel compatibility gate, stage->model spec
composition for stacked stage params, the HLO collective-count parser,
and the roofline price of the join collectives.

Subprocess tier (device count locks at jax init): gradient parity <=1e-5
(f32) for tensor-sharded 1F1B and GPipe — with and without sequence
parallelism — vs the replicated ``sequential_reference`` on a
``(stage=2, data=1, model=2)`` mesh; and an 8-device
``(stage=2, data=2, model=2)`` SPBEngine session whose compiled HLO
moves strictly fewer all-gather bytes than the replicated baseline
(the boundary weight gathers are gone), reduce-scatters grads under
ZeRO-2, truncates backward work per SPB depth, and still learns.
"""
import subprocess
import sys
import textwrap

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.analysis import hlo, roofline
from repro.config import SPBConfig, TrainConfig
from repro.configs import reduced_config
from repro.dist import steps as steps_lib
from repro.dist.pipeline import stage as st
from repro.models import lm

_ENV = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "HOME": "/root",
        "JAX_PLATFORMS": "cpu"}


def _run_sub(script: str, devices: int, ok: str, timeout: int = 600):
    pre = (f"import os\nos.environ['XLA_FLAGS'] = "
           f"'--xla_force_host_platform_device_count={devices}'\n")
    r = subprocess.run([sys.executable, "-c", pre + script],
                       capture_output=True, text=True, timeout=timeout,
                       env=_ENV)
    assert ok in r.stdout, f"stdout={r.stdout}\nstderr={r.stderr[-3000:]}"


# ---------------------------------------------------------------------------
# Compatibility gate + spec composition
# ---------------------------------------------------------------------------

def test_check_tensor_parallel_compatible():
    cfg = reduced_config("yi-6b")          # H=4, Hkv=2, d_ff divisible by 2
    st.check_tensor_parallel_compatible(cfg, 1)
    st.check_tensor_parallel_compatible(cfg, 2)
    with pytest.raises(ValueError, match="num_heads"):
        st.check_tensor_parallel_compatible(cfg, 3)
    moe = reduced_config("qwen3-moe-235b-a22b")
    with pytest.raises(ValueError, match="MoE"):
        st.check_tensor_parallel_compatible(moe, 2)
    ssd = reduced_config("mamba2-2.7b")
    with pytest.raises(ValueError, match="no tensor-parallel path"):
        st.check_tensor_parallel_compatible(ssd, 2)


def test_stage_param_specs_compose_stage_then_model():
    """Column weights put 'model' on the last dim of the per-stage view,
    row weights on the second-to-last, everything behind a leading
    'stage'; meshes without a model axis degrade to plain P('stage')."""
    cfg = reduced_config("yi-6b")
    stacked = jax.eval_shape(lambda: st.stack_stage_params(
        lm.init_lm(jax.random.key(0), cfg)["groups"], cfg, 2))
    mesh3 = jax.sharding.AbstractMesh((2, 2, 2), ("stage", "data", "model"))
    specs = st.stage_param_specs(stacked, mesh=mesh3)
    assert specs[0]["mixer"]["wq"] == P("stage", None, None, "model")
    assert specs[0]["mixer"]["wo"] == P("stage", None, "model")
    assert specs[0]["ffn"]["wu"] == P("stage", None, None, "model")
    assert specs[0]["ffn"]["wd"] == P("stage", None, "model")
    assert specs[0]["ln1"] == P("stage")
    mesh1 = jax.sharding.AbstractMesh((2,), ("stage",))
    flat = jax.tree.leaves(st.stage_param_specs(stacked, mesh=mesh1),
                           is_leaf=lambda x: isinstance(x, P))
    assert flat and all(s == P("stage") for s in flat)


def test_pipeline_step_rejects_bad_tp_combinations():
    cfg = reduced_config("yi-6b")
    tcfg = TrainConfig(microbatches=2)
    with pytest.raises(ValueError, match="sequence_parallel"):
        steps_lib.make_pipeline_train_step(
            cfg, tcfg, SPBConfig(), num_stages=2, sequence_parallel=True)
    with pytest.raises(ValueError, match="num_heads"):
        steps_lib.make_pipeline_train_step(
            cfg, tcfg, SPBConfig(), num_stages=2, tensor_parallel=3)


# ---------------------------------------------------------------------------
# HLO collective counts / payload volumes
# ---------------------------------------------------------------------------

_SYNTH_HLO = textwrap.dedent("""
    HloModule synth

    ENTRY %main (p0: f32[128]) -> f32[256] {
      %p0 = f32[128]{0} parameter(0)
      %ar = f32[128]{0} all-reduce(%p0), replica_groups=[2,2]<=[4]
      %ag = f32[256]{0} all-gather(%ar), replica_groups=[2,2]<=[4], dimensions={0}
      %rs = f32[128]{0} reduce-scatter(%ag), replica_groups=[2,2]<=[4]
      ROOT %o = f32[256]{0} all-gather(%rs), replica_groups=[2,2]<=[4], dimensions={0}
    }
""")


def test_hlo_collective_counts_and_payloads():
    """analyze() reports per-opcode counts and payload byte volumes on
    top of the ring wire model: all-gather/all-reduce payloads are the
    result bytes, reduce-scatter the operand bytes."""
    s = hlo.analyze(_SYNTH_HLO, num_partitions=4)
    c = s.collectives()
    assert c["all-reduce"]["count"] == 1
    assert c["all-gather"]["count"] == 2
    assert c["reduce-scatter"]["count"] == 1
    assert c["all-reduce"]["payload_bytes"] == 128 * 4
    assert c["all-gather"]["payload_bytes"] == 2 * 256 * 4
    assert c["reduce-scatter"]["payload_bytes"] == 256 * 4
    # wire model on group size n=2: AR 2(n-1)/n, AG/RS (n-1)/n
    assert c["all-reduce"]["wire_bytes"] == pytest.approx(512)
    assert c["all-gather"]["wire_bytes"] == pytest.approx(1024)
    assert c["reduce-scatter"]["wire_bytes"] == pytest.approx(512)
    assert s.num_collectives == 4


# ---------------------------------------------------------------------------
# Roofline: price of the TP join collectives per SPB depth
# ---------------------------------------------------------------------------

def test_roofline_tp_collective_bytes():
    cfg = reduced_config("yi-6b")          # 4 layers, f32, d_model=64
    kw = dict(microbatch=4, seq_len=128, num_stages=2, num_microbatches=4)
    # no model axis -> no join traffic
    assert roofline.pipeline_tp_collective_bytes(
        cfg, model_parallel=1, **kw) == 0.0
    full = roofline.pipeline_tp_collective_bytes(
        cfg, model_parallel=2, **kw)
    # closed form: M * layers/stage * 2 joins * 2(n-1)/n * act, fwd+bwd
    act = 4 * 128 * 64 * 4
    assert full == pytest.approx(4 * 2 * 2 * 1.0 * act * 2)
    # SPB truncation drops the frozen stages' backward joins
    trunc = roofline.pipeline_tp_collective_bytes(
        cfg, model_parallel=2, bwd_stages=1, **kw)
    assert trunc == pytest.approx(4 * 2 * 2 * 1.0 * act * 1.5)
    # sequence parallelism adds the stage-edge gathers, nothing more
    sp = roofline.pipeline_tp_collective_bytes(
        cfg, model_parallel=2, sequence_parallel=True, **kw)
    assert sp == pytest.approx(full + 4 * 0.5 * act * 2)
    # data sharding shrinks the activation and with it the traffic
    dp = roofline.pipeline_tp_collective_bytes(
        cfg, model_parallel=2, data_parallel=2, **kw)
    assert dp == pytest.approx(full / 2)
    with pytest.raises(ValueError, match="not divisible"):
        roofline.pipeline_tp_collective_bytes(
            cfg, model_parallel=2, data_parallel=3, **kw)


# ---------------------------------------------------------------------------
# Subprocess tier
# ---------------------------------------------------------------------------

_TP_GRAD_SCRIPT = textwrap.dedent("""
    import repro
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import reduced_config
    from repro.dist.pipeline import (pipeline_train_grads, schedules,
                                     sequential_reference)
    from repro.dist.pipeline import stage as st
    from repro.models import lm

    cfg = reduced_config("yi-6b")
    S, M, mb, seq = 2, 2, 2, 32
    params = lm.init_lm(jax.random.key(0), cfg)
    stacked = st.stack_stage_params(params["groups"], cfg, S)
    hp = st.head_params_of(params)
    head_loss = st.make_head_loss(cfg)
    xs = jax.random.normal(jax.random.key(1), (M, mb, seq, cfg.d_model),
                           jnp.float32) * 0.5
    labels = jax.random.randint(jax.random.key(2), (M, mb, seq), 0,
                                cfg.vocab_size)

    ref_fn = st.make_stage_fn(cfg)

    def ref_loss(p, h):
        ys = sequential_reference(ref_fn, p, xs)
        return jnp.mean(jnp.stack([head_loss(h, ys[m], labels[m])
                                   for m in range(M)]))

    want_l, (want_g, want_h) = jax.value_and_grad(
        ref_loss, argnums=(0, 1))(stacked, hp)

    mesh = jax.make_mesh((2, 1, 2), ("stage", "data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 3)
    pspecs = st.stage_param_specs(stacked, mesh=mesh)

    def close(got, want):
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5), got, want)

    for sp in (False, True):
        fn = st.make_stage_fn(cfg, tp_axis="model", sequence_parallel=sp)
        for kind in ("1f1b", "gpipe"):
            sched = schedules.build(kind, S, M)
            with jax.sharding.set_mesh(mesh):
                res = jax.jit(lambda p, x, t, h: pipeline_train_grads(
                    sched, fn, p, x, t, head_loss, head_params=h,
                    param_specs=pspecs, tensor_axis="model",
                    sequence_parallel=sp))(stacked, xs, labels, hp)
            np.testing.assert_allclose(float(res["loss"]), float(want_l),
                                       rtol=1e-6)
            close(res["stage_grads"], want_g)
            close(res["head_grads"], want_h)
            print(f"TP_GRADS_OK sp={sp} kind={kind}")
        # SPB truncation under TP: frozen stage exactly zero, live exact
        sched = schedules.one_f_one_b(S, M, bwd_stages=1)
        with jax.sharding.set_mesh(mesh):
            res = jax.jit(lambda p, x, t, h: pipeline_train_grads(
                sched, fn, p, x, t, head_loss, head_params=h,
                param_specs=pspecs, tensor_axis="model",
                sequence_parallel=sp))(stacked, xs, labels, hp)
        for g, w in zip(jax.tree.leaves(res["stage_grads"]),
                        jax.tree.leaves(want_g)):
            g, w = np.asarray(g), np.asarray(w)
            assert np.all(g[0] == 0)
            np.testing.assert_allclose(g[1], w[1], rtol=1e-5, atol=1e-5)
    print("ALL_TP_GRADS_OK")
""")


@pytest.mark.slow
def test_tensor_sharded_gradients_match_sequential_autodiff():
    """Tentpole pin: tensor-sharded 1F1B and GPipe — column/row-split
    weights, explicit psum joins, optional sequence-parallel layout —
    reproduce the replicated sequential reference's loss and gradients to
    <=1e-5 (f32) on a (stage=2, data=1, model=2) mesh, and SPB-truncated
    schedules still zero exactly the frozen stages."""
    _run_sub(_TP_GRAD_SCRIPT, 4, "ALL_TP_GRADS_OK", timeout=900)


_TP_ENGINE_SCRIPT = textwrap.dedent("""
    import repro
    import jax
    from repro.analysis import hlo
    from repro.config import SPBConfig, TrainConfig
    from repro.configs import make_batch, reduced_config
    from repro.engine import SPBEngine
    from repro.launch.mesh import make_pipeline_mesh

    cfg = reduced_config("yi-6b")
    tcfg = TrainConfig(optimizer="adamw", learning_rate=3e-3,
                       microbatches=2)
    spb = SPBConfig(mode="temporal", k=2)
    mesh = make_pipeline_mesh(2, data_parallel=2, model_parallel=2)
    batch = make_batch(cfg, 8, 64)

    base = SPBEngine(cfg, tcfg, spb, mesh=mesh, parallelism="pipeline",
                     tensor_parallel=1, donate=False)
    tp = SPBEngine(cfg, tcfg, spb, mesh=mesh, parallelism="pipeline",
                   zero2=True, donate=False)
    assert tp.tensor_parallel == 2         # defaults to the model axis
    specs = base.batch_specs_like(batch)
    b_txt = base.lower_step(specs, depth=None).compile().as_text()
    t_txt = tp.lower_step(specs, depth=None).compile().as_text()
    cb = hlo.analyze(b_txt, num_partitions=8).collectives()
    ct = hlo.analyze(t_txt, num_partitions=8).collectives()
    # HLO proof: the replicated baseline all-gathers the model-sharded
    # stage weights at the shard_map boundary every step; the tensor-
    # sharded step consumes them in place
    ag = lambda c: c.get("all-gather", {"payload_bytes": 0})["payload_bytes"]
    assert ag(ct) < ag(cb), (ag(ct), ag(cb))
    # ZeRO-2: grads leave the pipe via reduce-scatter over 'data'
    assert ct.get("reduce-scatter", {"count": 0})["count"] > 0
    print("TP_HLO_OK", int(ag(cb)), int(ag(ct)))

    # SPB truncation still elides frozen-stage backward under TP
    trunc = tp.lower_step(specs, depth=2).compile().as_text()
    assert "pipeline_bwd_stage1" in trunc
    assert "pipeline_bwd_stage0" not in trunc
    print("TP_ELISION_OK")

    # the 3-D session learns, and the AOT signature keys on the layout
    tp.init_state(jax.random.key(0))
    hist = [float(tp.train_step(batch, s)["loss"]) for s in range(6)]
    assert hist[-1] < hist[0], hist
    assert base._step_signature() != tp._step_signature()
    print("TP_ENGINE_OK")
""")


@pytest.mark.slow
def test_tensor_sharded_engine_hlo_and_session():
    """8-device (stage=2, data=2, model=2) SPBEngine: tensor sharding
    removes the boundary weight all-gathers from the compiled HLO, ZeRO-2
    reduce-scatters gradients, SPB depth still elides frozen backward
    scopes, and the session learns."""
    _run_sub(_TP_ENGINE_SCRIPT, 8, "TP_ENGINE_OK", timeout=900)
