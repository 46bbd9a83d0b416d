"""ZeRO-1 optimizer-state sharding: the DP shard dim must be the LARGEST
divisible not-yet-sharded dim (not the first), locked here so the choice
cannot silently regress."""
import types

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.config import TrainConfig
from repro.configs import reduced_config
from repro.dist import sharding as shd
from repro.dist import steps as steps_lib


def _mesh(shape=(4, 1), axes=("data", "model")):
    """Spec derivation is pure — a stub with axis_names/devices suffices,
    so the test does not need 4 real devices."""
    return types.SimpleNamespace(axis_names=axes, devices=np.empty(shape))


def test_zero1_prefers_largest_divisible_dim():
    mesh = _mesh()
    # both dims divisible by dp=4: dim1 (256) wins over dim0 (8)
    assert shd.zero1_spec(P(), (8, 256), mesh) == P(None, "data")
    # first-dim-only divisibility still works
    assert shd.zero1_spec(P(), (8, 3), mesh) == P("data")
    # tie broken by first occurrence of the max
    assert shd.zero1_spec(P(), (64, 64), mesh) == P("data")


def test_zero1_respects_existing_axes():
    mesh = _mesh()
    # dim0 already on 'model': dp goes to the largest FREE dim
    assert shd.zero1_spec(P("model", None), (512, 64), mesh) == \
        P("model", "data")
    # dp axis already used somewhere: leave the spec alone
    assert shd.zero1_spec(P("data", None), (8, 256), mesh) == \
        P("data", None)
    # nothing divisible: unchanged
    assert shd.zero1_spec(P(), (3, 5), mesh) == P()
    # no dp axes in the mesh at all: unchanged
    assert shd.zero1_spec(P(), (8, 256), _mesh((4,), ("model",))) == P()


def test_zero1_multi_pod_axes():
    mesh = _mesh((2, 2, 1), ("pod", "data", "model"))     # dp = 4
    assert shd.zero1_spec(P(), (4, 64), mesh) == P(None, ("pod", "data"))


def test_state_pspec_zero1_locked_specs():
    """Lock the chosen specs for the reduced yi-6b AdamW state: every
    ZeRO-1-sharded leaf uses its largest divisible free dim."""
    cfg = reduced_config("yi-6b")          # d_model=64, q_dim=64, vocab 512
    tcfg = TrainConfig(optimizer="adamw")
    shapes = steps_lib.train_state_shapes(cfg, tcfg)
    mesh = _mesh()
    specs = shd.state_pspec(shapes, mesh=mesh, zero1=True)

    # embedding moments: (padded_vocab=512, d_model=64) with dim0 already
    # on 'model' -> dp lands on d_model
    assert specs["opt"]["mu"]["embed"]["tok"] == P("model", "data")
    # attention wq moments: stacked (count=4, d_model=64, q_dim=64), last
    # dim on 'model' -> dp picks d_model (64 > count=4)
    assert specs["opt"]["mu"]["groups"][0][0]["mixer"]["wq"] == \
        P(None, "data", "model")
    # params themselves are never ZeRO-sharded
    assert specs["params"]["groups"][0][0]["mixer"]["wq"] == \
        P(None, None, "model")
    assert specs["step"] == P()

    # invariant over every opt leaf: if dp was added, it sits on the
    # largest divisible dim that the base spec left free
    dp_size = 4
    base = {k: shd.params_pspec(v, mesh=mesh)
            for k, v in shapes["opt"].items()}

    def check(bspec, zspec, leaf):
        b = list(bspec) + [None] * (len(leaf.shape) - len(bspec))
        z = list(zspec) + [None] * (len(leaf.shape) - len(zspec))
        added = [i for i, (x, y) in enumerate(zip(b, z)) if x != y]
        if not added:
            return
        (i,) = added
        assert z[i] == "data"
        free_divisible = [leaf.shape[j] for j, e in enumerate(b)
                          if e is None and leaf.shape[j] % dp_size == 0
                          and leaf.shape[j] >= dp_size]
        assert leaf.shape[i] == max(free_divisible)

    for key in shapes["opt"]:
        jax.tree.map(
            lambda b, z, l: check(b, z, l), base[key],
            specs["opt"][key], shapes["opt"][key],
            is_leaf=lambda x: isinstance(x, P))


def test_zero1_composes_with_pipeline_state_pspec():
    """On a (stage=2, data=2) mesh the stage rule claims the scanned
    leading layer dim FIRST, then ZeRO-1 shards each optimizer moment
    over 'data' on another dim — params stay replicated across 'data'
    within a stage while their moments are data-sharded."""
    cfg = reduced_config("yi-6b")
    tcfg = TrainConfig(optimizer="adamw")
    shapes = steps_lib.train_state_shapes(cfg, tcfg)
    mesh = jax.sharding.AbstractMesh((2, 2), ("stage", "data"))
    specs = shd.pipeline_state_pspec(shapes, mesh=mesh, zero1=True)

    # params: stage on the layer dim, never 'data'
    p_leaves = jax.tree.leaves(specs["params"]["groups"],
                               is_leaf=lambda x: isinstance(x, P))
    assert p_leaves
    for s in p_leaves:
        assert s[0] == "stage"
        assert "data" not in jax.tree.leaves(tuple(s))
    # moments: stage preserved on dim0 AND 'data' on some later dim
    # whenever one is divisible (wq moments (4, 64, 64): ZeRO-1 picks the
    # first of the tied largest free dims -> dim1)
    assert specs["opt"]["mu"]["groups"][0][0]["mixer"]["wq"] == \
        P("stage", "data")
    mu_leaves = jax.tree.leaves(specs["opt"]["mu"]["groups"],
                                is_leaf=lambda x: isinstance(x, P))
    assert all(s[0] == "stage" for s in mu_leaves)
    assert any("data" in tuple(s) for s in mu_leaves)
    # the stage dim is never double-claimed by ZeRO-1
    for s in mu_leaves:
        flat = [a for e in tuple(s) if e is not None
                for a in (e if isinstance(e, tuple) else (e,))]
        assert flat.count("stage") == 1
    # off-pipe leaves (embedding/head moments) still ZeRO-shard over data
    assert "data" in tuple(specs["opt"]["mu"]["embed"]["tok"])
    assert specs["params"]["final_norm"] == P()
    assert specs["step"] == P()


def test_pipeline_state_pspec_without_zero1_keeps_data_free():
    cfg = reduced_config("yi-6b")
    shapes = steps_lib.train_state_shapes(cfg, TrainConfig())
    mesh = jax.sharding.AbstractMesh((2, 2), ("stage", "data"))
    specs = shd.pipeline_state_pspec(shapes, mesh=mesh, zero1=False)
    for tree in (specs["params"], specs["opt"]):
        for s in jax.tree.leaves(tree, is_leaf=lambda x: isinstance(x, P)):
            assert "data" not in tuple(s)


# ---------------------------------------------------------------------------
# 3-D (stage, data, model) composition: dp_partition_plan / ZeRO-2
# ---------------------------------------------------------------------------

_MESH3D = jax.sharding.AbstractMesh((2, 2, 2), ("stage", "data", "model"))


def test_dp_partition_plan_skips_claimed_dims():
    """The plan never lands on a dim stage/model already claimed, even
    when that dim is the largest divisible one."""
    # dim2 largest but on 'model'; dim0 on 'stage' -> dim1 wins
    assert shd.dp_partition_plan(P("stage", None, "model"),
                                 (4, 64, 128), _MESH3D) == (1, ("data",))
    # every free dim indivisible -> no plan
    assert shd.dp_partition_plan(P("stage", None, "model"),
                                 (4, 3, 128), _MESH3D) is None
    # spec already touching a dp axis -> leave alone
    assert shd.dp_partition_plan(P("stage", "data"),
                                 (4, 64, 128), _MESH3D) is None


def test_zero2_spec_matches_zero1_plan():
    """ZeRO-2 grads shard exactly like the ZeRO-1 moments — same plan,
    same dim — so the optimizer's elementwise update is shard-local."""
    for spec, shape in [(P("stage", None, None, "model"), (2, 2, 64, 32)),
                        (P("stage", None, "model"), (2, 128, 64)),
                        (P("stage",), (2, 2, 64)),
                        (P(), (512, 64))]:
        assert shd.zero2_spec(spec, shape, _MESH3D) == \
            shd.zero1_spec(spec, shape, _MESH3D)


def test_zero1_composes_with_model_on_3d_mesh():
    """Stage claims dim0, the tensor-parallel column rule claims the last
    dim, and ZeRO-1 shards the moments over 'data' on the largest dim
    left — the full stage -> model -> ZeRO composition order."""
    cfg = reduced_config("yi-6b")
    tcfg = TrainConfig(optimizer="adamw")
    shapes = steps_lib.train_state_shapes(cfg, tcfg)
    specs = shd.pipeline_state_pspec(shapes, mesh=_MESH3D, zero1=True)
    # wq: (count=4, d_model=64, q_dim=64) -> stage, data, model
    assert specs["params"]["groups"][0][0]["mixer"]["wq"] == \
        P("stage", None, "model")
    assert specs["opt"]["mu"]["groups"][0][0]["mixer"]["wq"] == \
        P("stage", "data", "model")
    # row-parallel wo: model on the second-to-last dim
    assert specs["params"]["groups"][0][0]["mixer"]["wo"] == \
        P("stage", "model")
    assert specs["opt"]["mu"]["groups"][0][0]["mixer"]["wo"] == \
        P("stage", "model", "data")
    # norm scales: (4, 64) -> stage + data, nothing for model to claim
    assert specs["opt"]["mu"]["groups"][0][0]["ln1"] == P("stage", "data")


def test_param_leaf_spec_matches_param_spec_on_views():
    """stage_param_specs specs the per-stage view (shape[1:]) of each
    stacked leaf; param_leaf_spec must agree with the full-tree rule."""
    cfg = reduced_config("yi-6b")
    shapes = steps_lib.train_state_shapes(cfg, TrainConfig())

    def check(path, leaf):
        want = shd.params_pspec(shapes["params"], mesh=_MESH3D)
        got = shd.param_leaf_spec(path, leaf.shape, mesh=_MESH3D)
        node = want
        for p_ in path:
            node = node[getattr(p_, "key", getattr(p_, "idx", p_))]
        assert got == node, (path, got, node)

    jax.tree_util.tree_map_with_path(check, shapes["params"])


def test_sharded_state_bytes_shrink_by_mesh_factors():
    """Acceptance pin: per-device state bytes on the 3-D mesh shrink by
    ~model for the column/row-sharded leaves (and by data for moments)
    versus the same state on a (stage, data) mesh."""
    cfg = reduced_config("yi-6b")
    tcfg = TrainConfig(optimizer="adamw")
    shapes = steps_lib.train_state_shapes(cfg, tcfg)
    mesh2d = jax.sharding.AbstractMesh((2, 2), ("stage", "data"))
    b3 = shd.sharded_state_bytes(
        shapes, shd.pipeline_state_pspec(shapes, mesh=_MESH3D, zero1=True),
        _MESH3D)
    b2 = shd.sharded_state_bytes(
        shapes, shd.pipeline_state_pspec(shapes, mesh=mesh2d, zero1=True),
        mesh2d)
    assert b3 < b2
    # the stage-stacked params alone shrink by exactly stage * model for
    # the matrix leaves; norm scales only see the stage factor
    p3 = shd.pipeline_state_pspec(shapes, mesh=_MESH3D)["params"]["groups"]
    g3 = shd.sharded_state_bytes(shapes["params"]["groups"], p3, _MESH3D)
    repl = jax.tree.map(lambda s: P(), p3,
                        is_leaf=lambda x: isinstance(x, P))
    g0 = shd.sharded_state_bytes(shapes["params"]["groups"], repl, _MESH3D)
    assert g0 / g3 > 3.5        # ~stage(2) * model(2) minus the scales
