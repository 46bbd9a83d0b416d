"""Spatial co-location invariants (PR 8): disjoint submesh partitioning,
the process-wide step cache, AOT artifact dedupe, concurrent placement
rounds, elastic resize parity and horizontal fusion.

Fast tests run on whatever devices the pytest process has (1 is enough);
multi-device flows run in ``slow``-marked subprocesses that force
``xla_force_host_platform_device_count``.
"""
import json
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.launch.mesh import assert_disjoint, make_submeshes, split_devices

ENV = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "HOME": "/root",
       "JAX_PLATFORMS": "cpu",
       "XLA_FLAGS": "--xla_force_host_platform_device_count=2"}


# ---------------------------------------------------------------------------
# Submesh partitioning (pure bookkeeping — no devices needed)
# ---------------------------------------------------------------------------

def test_split_devices_partitions_prefix():
    groups = split_devices([2, 1, 3], devices=list(range(8)))
    assert groups == [[0, 1], [2], [3, 4, 5]]       # contiguous, ordered
    flat = [d for g in groups for d in g]
    assert len(flat) == len(set(flat))              # disjoint


def test_split_devices_rejects_bad_sizes():
    with pytest.raises(ValueError):
        split_devices([2, 2], devices=list(range(3)))   # not enough
    with pytest.raises(ValueError):
        split_devices([], devices=list(range(3)))
    with pytest.raises(ValueError):
        split_devices([1, 0], devices=list(range(3)))


def test_make_submeshes_single_device():
    (mesh,) = make_submeshes(count=1)
    assert mesh.devices.shape == (len(mesh.devices.flat), 1)
    assert tuple(mesh.axis_names) == ("data", "model")
    with pytest.raises(ValueError):
        make_submeshes(count=10 ** 6)
    with pytest.raises(ValueError):
        make_submeshes(sizes=[1], count=1)          # exactly one selector


def test_assert_disjoint_catches_shared_device():
    (a,) = make_submeshes(count=1)
    (b,) = make_submeshes(count=1)                  # same devices again
    with pytest.raises(ValueError, match="appears in submesh"):
        assert_disjoint([a, b])


def test_split_devices_even_split_takes_remainder_first():
    # make_submeshes(count=3) over 5 devices splits [2, 2, 1]
    groups = split_devices([2, 2, 1], devices=list(range(5)))
    assert [len(g) for g in groups] == [2, 2, 1]
    assert [d for g in groups for d in g] == list(range(5))


# ---------------------------------------------------------------------------
# Cross-job compiled-step cache + AOT artifact dedupe
# ---------------------------------------------------------------------------

def _engine(seed, *, k=2, shared=True, arch="yi-6b"):
    from repro.config import SPBConfig, TrainConfig
    from repro.configs import reduced_config
    from repro.engine import SPBEngine

    return SPBEngine(reduced_config(arch), TrainConfig(seed=seed,
                                                       num_steps=16),
                     SPBConfig(mode="temporal", k=k), shared_cache=shared)


def test_step_cache_cross_engine_hit():
    """Tenant 2 with the same (config, depth, mesh) never re-jits: its
    first step is a GLOBAL table hit, and entries stay at the number of
    distinct step shapes — not the number of tenants."""
    import jax

    from repro.configs import reduced_config
    from repro.data.pipeline import Pipeline
    from repro.engine import stepcache

    batch = Pipeline(reduced_config("yi-6b"), 2, 16, seed=0).get_batch(0)
    stepcache.GLOBAL.clear()
    a, b = _engine(0), _engine(1)
    a.init_state(jax.random.key(0))
    b.init_state(jax.random.key(1))
    la = float(a.train_step(batch, 0, depth=2)["loss"])
    miss_stats = stepcache.GLOBAL.stats()
    lb = float(b.train_step(batch, 0, depth=2)["loss"])
    hit_stats = stepcache.GLOBAL.stats()
    assert miss_stats["misses"] >= 1
    assert hit_stats["hits"] >= 1
    assert hit_stats["entries"] == miss_stats["entries"]    # no new entry
    assert la != lb                     # distinct seeds: shared code only


def test_step_cache_keys_distinguish_depth_and_mesh():
    from repro.engine import stepcache

    e = _engine(0)
    k2 = e.step_cache_key(2)
    k4 = e.step_cache_key(4)
    assert k2 != k4                     # depth participates
    fp = stepcache.mesh_fingerprint(e.mesh)
    assert k2[-1] == fp                 # device identity participates
    assert fp == stepcache.mesh_fingerprint(e.mesh)     # and is stable


def test_aot_cache_path_dedupes_across_seeds(tmp_path):
    """Same (config, depths, parallelism, submesh) => same artifact path
    even for different job seeds; different arch or k => different."""
    from repro.configs import reduced_config
    from repro.data.pipeline import Pipeline

    batch = Pipeline(reduced_config("yi-6b"), 2, 16, seed=0).get_batch(0)
    a, b = _engine(0), _engine(7)
    sa = a.batch_specs_like(batch)
    sb = b.batch_specs_like(batch)
    root = str(tmp_path)
    assert a.aot_cache_path(sa, root) == b.aot_cache_path(sb, root)
    c = _engine(0, k=4)                 # different depth set
    assert c.aot_cache_path(c.batch_specs_like(batch), root) \
        != a.aot_cache_path(sa, root)


@pytest.mark.parametrize("from_env", [True, False])
def test_compilation_cache_is_placed_from_outside(from_env, tmp_path,
                                                  monkeypatch):
    """``JAX_COMPILATION_CACHE_DIR`` wins and nothing else is set in code;
    without it the cache goes to the one fixed path in the checkout."""
    import jax

    from repro.engine import stepcache
    knobs = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_entry_size_bytes",
             "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in knobs}
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        path = stepcache.enable_compilation_cache()
        if from_env:
            assert path == tmp_path
            assert jax.config.jax_compilation_cache_dir == \
                saved["jax_compilation_cache_dir"]
        else:
            assert path == stepcache.DEFAULT_COMPILATION_CACHE
            assert path == Path(__file__).resolve().parents[1] / ".jax_cache"
            assert jax.config.jax_compilation_cache_dir == str(path)
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)


# ---------------------------------------------------------------------------
# Concurrent placement rounds (DES level — no jax steps)
# ---------------------------------------------------------------------------

def _specs(n=2, iters=4, workers=2, arrival=0.31):
    from repro.cluster.runtime import JobSpec, WorkerSpec

    return [JobSpec(job_id=i, arrival=i * arrival, model="m",
                    model_size_gb=0.01, iterations=iters,
                    workers=[WorkerSpec(duration=0.5 + 0.1 * i, memory=0.5)
                             for _ in range(workers)])
            for i in range(n)]


def _run(backend, specs, **kw):
    from repro.cluster import ClusterRuntime
    from repro.jigsaw.schedulers import JigsawScheduler

    return ClusterRuntime(specs, JigsawScheduler(), backend,
                          num_machines=2, gamma=0.05, horizon=1e9,
                          record_schedule=True, **kw).run()


def test_concurrent_rounds_match_sequential_des():
    """With per-event rounds (quantum 0) the threaded Phase A/B/C commit
    is result-identical to the serial path on the DES backend."""
    from repro.cluster import SimBackend

    class _ConcSim(SimBackend):
        concurrent_rounds = True

    seq = _run(SimBackend(), _specs())
    conc = _run(_ConcSim(), _specs(), round_quantum=0.0)
    assert conc.jct == seq.jct
    assert conc.makespan == seq.makespan
    assert conc.schedule == seq.schedule
    assert conc.util == seq.util


def test_round_quantum_batches_events_deterministically():
    """A nonzero quantum merges near-simultaneous events into one
    placement round; the session still completes every job, keeps
    machine exclusivity, and is run-to-run deterministic."""
    from repro.cluster import SimBackend

    class _ConcSim(SimBackend):
        concurrent_rounds = True

    a = _run(_ConcSim(), _specs(arrival=0.0), round_quantum=0.5)
    b = _run(_ConcSim(), _specs(arrival=0.0), round_quantum=0.5)
    assert a.schedule == b.schedule and a.jct == b.jct
    assert len(a.jct) == 2
    by_machine = {}
    for m, s, e, *_ in a.schedule:
        by_machine.setdefault(m, []).append((s, e))
    for ivs in by_machine.values():
        ivs.sort()
        for (_s1, e1), (s2, _e2) in zip(ivs, ivs[1:]):
            assert s2 >= e1 - 1e-9


def test_round_quantum_ignored_on_sequential_backend():
    from repro.cluster import SimBackend

    base = _run(SimBackend(), _specs())
    with_q = _run(SimBackend(), _specs(), round_quantum=5.0)
    assert base.schedule == with_q.schedule
    assert base.jct == with_q.jct


def test_round_quantum_validation():
    from repro.cluster import ClusterRuntime, SimBackend
    from repro.jigsaw.schedulers import JigsawScheduler

    with pytest.raises(ValueError):
        ClusterRuntime(_specs(), JigsawScheduler(), SimBackend(),
                       num_machines=2, round_quantum=-0.1)


# ---------------------------------------------------------------------------
# Multi-device flows (subprocesses force 2 virtual devices)
# ---------------------------------------------------------------------------

_RESIZE_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax
    import numpy as np
    from repro.config import SPBConfig, TrainConfig
    from repro.configs import reduced_config
    from repro.data.pipeline import Pipeline
    from repro.engine import SPBEngine
    from repro.launch.mesh import assert_disjoint, make_submeshes

    subs = make_submeshes(count=2)
    assert_disjoint(subs)
    assert [len(list(m.devices.flat)) for m in subs] == [1, 1]

    cfg = reduced_config("yi-6b")
    mk = lambda: SPBEngine(cfg, TrainConfig(seed=0, num_steps=16),
                           SPBConfig(mode="temporal", k=2), mesh=subs[0])
    pipe = Pipeline(cfg, 2, 16, seed=0)

    moved, stay = mk(), mk()
    moved.init_state(jax.random.key(0))
    stay.init_state(jax.random.key(0))

    losses = {"moved": [], "stay": []}
    for step in range(6):
        if step == 2:
            moved.resize(subs[1])      # scheduler moved the job
        if step == 4:
            moved.resize(subs[0])      # ... and moved it back
        b = pipe.get_batch(step)
        losses["moved"].append(float(moved.train_step(b, step)["loss"]))
        losses["stay"].append(float(stay.train_step(b, step)["loss"]))
    np.testing.assert_allclose(losses["moved"], losses["stay"],
                               rtol=2e-4, atol=1e-6)
    assert {d.id for d in moved.mesh.devices.flat} \\
        == {d.id for d in subs[0].devices.flat}
    print("RESIZE_OK")
""")


@pytest.mark.slow
def test_resize_round_trip_parity():
    """Moving a job across disjoint submeshes and back (the burst-
    parallel reshard path) is numerically a no-op vs never moving."""
    r = subprocess.run([sys.executable, "-c", _RESIZE_SCRIPT],
                       capture_output=True, text=True, timeout=900, env=ENV)
    assert r.returncode == 0, r.stderr[-4000:]
    assert "RESIZE_OK" in r.stdout


_FUSED_SCRIPT = textwrap.dedent("""
    import jax
    import numpy as np
    from repro.config import SPBConfig, TrainConfig
    from repro.configs import reduced_config
    from repro.engine import FusedEngine, SPBEngine, stack_batches
    from repro.data.pipeline import Pipeline

    cfg = reduced_config("yi-6b")
    tcfg = TrainConfig(seed=0, num_steps=16)
    spb = SPBConfig(mode="temporal", k=2)
    seeds = [0, 1]

    fused = FusedEngine(cfg, tcfg, spb, num_jobs=2)
    fused.init_states(seeds)
    solos = []
    for s in seeds:
        e = SPBEngine(cfg, tcfg, spb)
        e.init_state(jax.random.key(s))
        solos.append(e)

    pipes = [Pipeline(cfg, 2, 16, seed=s) for s in seeds]
    for step in range(4):
        batches = [p.get_batch(step) for p in pipes]
        fm = fused.per_job_metrics(
            fused.train_step(stack_batches(batches), step))
        for j, e in enumerate(solos):
            sm = e.train_step(batches[j], step)
            np.testing.assert_allclose(
                float(fm[j]["loss"]), float(sm["loss"]),
                rtol=1e-5, atol=1e-6)
            np.testing.assert_allclose(
                float(fm[j]["xent"]), float(sm["xent"]),
                rtol=1e-5, atol=1e-6)
    print("FUSED_OK")
""")


@pytest.mark.slow
def test_fused_vmap_matches_per_job_steps():
    """One vmapped train step over stacked jobs == each job stepped
    alone (per-job losses within 1e-5)."""
    r = subprocess.run([sys.executable, "-c", _FUSED_SCRIPT],
                       capture_output=True, text=True, timeout=900,
                       env={**ENV, "XLA_FLAGS":
                            "--xla_force_host_platform_device_count=1"})
    assert r.returncode == 0, r.stderr[-4000:]
    assert "FUSED_OK" in r.stdout


@pytest.mark.slow
def test_spatial_live_session_end_to_end(tmp_path):
    """The CLI flow the CI smoke runs: 2 jobs on 2 disjoint submeshes,
    genuinely concurrent rounds, cross-job step-cache hits."""
    out = tmp_path / "session.json"
    # a compilation cache of its own, empty: compiles served warm from the
    # checkout's cache reorder the rounds this test reads
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.cluster", "--jobs", "2",
         "--machines", "2", "--workers", "2", "--iters", "2",
         "--arrival", "0.0", "--spatial", "--quiet",
         "--json-out", str(out)],
        capture_output=True, text=True, timeout=900,
        env={**ENV, "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax_cache")})
    assert r.returncode == 0, r.stderr[-4000:]
    rec = json.loads(out.read_text())
    assert rec["spatial"] is True
    assert len(rec["jct"]) == 2
    assert rec["max_concurrent_tasks"] == 2         # rounds overlapped
    # workers bounce across both submeshes, so job 1 reuses job 0's
    # (config, depth, submesh) step-cache entries: hits, not re-jits
    assert rec["stepcache"]["hits"] >= 1
    assert rec["stepcache"]["misses"] < 2 * 2 * 2 * 2   # not one per task
    assert sum(rec["resizes"].values()) >= 1        # elastic moves happened
    for s in rec["summary"].values():
        assert s["steps_run"] == 2 * 2
