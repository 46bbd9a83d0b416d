"""The program's spans and counters (``repro.obs``): a tiny temporal-SPB
session traced on the CPU, its trace read back with ``ProfileData``."""
import gc
import glob
import os

import jax
import numpy as np
import pytest

from repro import obs
from repro.config import SPBConfig, TrainConfig
from repro.configs import reduced_config
from repro.data.pipeline import Pipeline
from repro.engine import SPBEngine

STEPS = 3


def _host_spans(trace_dir):
    """{name: [(line, start_ns, end_ns, stats)]} of the program's spans."""
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    out = {name: [] for name in obs.SPANS}
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name in out:
                    out[e.name].append(((plane.name, i), int(e.start_ns),
                                        int(e.end_ns), dict(e.stats)))
    return out


def _inside(child, parent):
    return (child[0] == parent[0] and parent[1] <= child[1]
            and child[2] <= parent[2])


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    cfg = reduced_config("yi-6b")
    engine = SPBEngine(cfg, TrainConfig(optimizer="adamw", num_steps=10),
                       SPBConfig(mode="temporal", k=2))
    engine.init_state(jax.random.key(0))
    pipe = Pipeline(cfg, 2, 32, seed=0)
    d = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(d):
        for step in range(STEPS):
            m = engine.train_step(pipe.get_batch(step), step)
        jax.block_until_ready(m)
        gc.collect()
    keys = [engine.depth_key_for_step(s) for s in range(STEPS)]
    return _host_spans(d), keys


def test_train_step_spans_carry_their_stats_and_the_enqueue(traced):
    spans, keys = traced
    steps = spans["engine.train_step"]
    assert [s[3]["step"] for s in steps] == list(range(STEPS))
    # the profiler reads a numeric string back as a number
    assert [str(s[3]["depth"]) for s in steps] == [str(k) for k in keys]
    assert len(set(keys)) == 2
    compiles = [s[3]["compiles"] for s in steps]
    assert compiles == sorted(compiles) and compiles[0] >= 1
    for s in steps:
        assert sum(_inside(e, s) for e in spans["engine.enqueue"]) == 1


def test_sample_lies_inside_get_batch(traced):
    spans, _ = traced
    batches = spans["data.get_batch"]
    assert [b[3]["step"] for b in batches] == list(range(STEPS))
    assert len(spans["data.sample"]) == STEPS
    for b in batches:
        assert sum(_inside(s, b) for s in spans["data.sample"]) == 1


def test_gc_collect_is_a_span(traced):
    spans, _ = traced
    assert any(s[3]["generation"] == 2 for s in spans["host.gc"])
    assert all(s[2] > s[1] for s in spans["host.gc"])


def test_compiles_count_new_shapes_not_cached_calls():
    obs.install()
    obs.install()                       # a second call hooks nothing more
    f = jax.jit(lambda x: x * 2 + 1)
    f(np.ones(3, np.float32)).block_until_ready()
    before = obs.counters()["compiles"]
    f(np.ones(3, np.float32)).block_until_ready()
    assert obs.counters()["compiles"] == before
    f(np.ones(5, np.float32)).block_until_ready()
    assert obs.counters()["compiles"] == before + 1


def test_span_names_come_from_the_table():
    assert not any(name.startswith("bench.") for name in obs.SPANS)
    with pytest.raises(KeyError):
        obs.span("engine.unknown")
