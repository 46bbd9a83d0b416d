"""The readers of the program's spans (``perfbench/spans.py`` and the five
metrics built on it).

On a small trace recorded on a TPU v5e through the harness
(``harness.run`` with ``trace=True``; the yi spb_k2 cell at 2 layers, 4
heads of 128 as the flash kernels tile, 2 x 256 tokens, a few window
steps), committed under ``data/`` with a sidecar that holds the cell as
run, the chip's peaks and the result line the harness printed there; and
on a trace recorded here on the CPU through the harness's own window."""
import json
import statistics
from pathlib import Path

import pytest

from perfbench import harness, readers, registry, spans, tracefile
from perfbench.tests import tiny_cells

DATA = Path(__file__).resolve().parent / "data"
CHIP = "yi6b_2l.spb_k2.spans"
NEW = ("data_sample_ms", "data_idle_ms", "engine_host_ms",
       "compiles_in_window", "gc_ms")
HOST_ONLY = ("data_sample_ms", "engine_host_ms", "compiles_in_window",
             "gc_ms")
MS = 1e6


def _read(name, rec):
    return registry.reader(name)(rec)


@pytest.fixture(scope="module")
def chip():
    """The chip trace's record as the harness builds it, with the window's
    step depths (the cycle 2, 1 from the job's fourth step on)."""
    path = str(DATA / f"{CHIP}.xplane.pb")
    side = json.loads((DATA / f"{CHIP}.json").read_text())
    tr = tracefile.load(path)
    (lo, hi), = [(a, b) for a, b, n in tr["host"] if n == "bench.window"]
    n = sum(1 for *_, nm in tr["host"] if nm == "bench.train_step")
    first = harness.CHECK_STEPS           # the first max(k, 3) steps
    depths = [[2, 1][(first + i) % 2] for i in range(n)]
    rec = {"trace_path": path, "hf": side["hf"], "job": side["job"],
           "peaks": side["peaks"], "chips": 1,
           "steps": [{"depth": d} for d in depths],
           "trace": tracefile.summarize(tr, lo, hi, depths)}
    return rec, tr, side


def test_every_reader_gives_a_number(chip):
    rec, _, side = chip
    for name in NEW:
        v = _read(name, rec)
        assert isinstance(v, (int, float)) and v >= 0, (name, v)
        # the same number the harness printed on the chip
        assert v == pytest.approx(side["result"]["metrics"][name]["value"])


def test_inner_spans_fit_inside_the_benchmarks(chip):
    rec, tr, _ = chip
    lo, hi = spans.window(rec)["lo"], spans.window(rec)["hi"]

    def bench_ms(name):
        return statistics.median(b - a for a, b, n in tr["host"]
                                 if n == name and lo <= a and b <= hi) / MS

    assert _read("data_sample_ms", rec) <= bench_ms("bench.get_batch")
    assert _read("engine_host_ms", rec) <= bench_ms("bench.train_step")
    assert _read("compiles_in_window", rec) == 0


def test_long_idle_gaps_meet_a_program_span(chip):
    """Host and device share the trace's clock: every idle gap of 1 ms or
    more on the device overlaps a span of the program."""
    rec, _, _ = chip
    w = spans.window(rec)
    every = [s for v in w["spans"].values() for s in v]
    long_gaps = [(a, b) for a, b in spans.idle_gaps(w) if b - a >= MS]
    assert long_gaps
    for a, b in long_gaps:
        assert any(s[1] < b and s[2] > a for s in every), (a, b)
    # the data layer's idle time is a part of the window's idle time
    idle = sum(b - a for a, b in spans.idle_gaps(w))
    assert 0 < spans.idle_under(w, "data.") <= idle


def test_steps_are_told_apart_by_their_stat(chip):
    rec, _, _ = chip
    w = spans.window(rec)
    steps = [s[3]["step"] for s in w["spans"]["engine.train_step"]]
    batches = [s[3]["step"] for s in w["spans"]["data.get_batch"]]
    assert steps == batches == list(range(harness.CHECK_STEPS,
                                          harness.CHECK_STEPS + len(steps)))
    assert [str(s[3]["depth"]) for s in w["spans"]["engine.train_step"]] \
        == [str(s["depth"]) for s in rec["steps"]]


def test_existing_readers_unchanged_by_program_spans(chip):
    """``tracefile`` keeps only the benchmark's spans, so the program's
    leave the existing reduction as it was: idle gaps labelled by
    ``bench.*`` spans alone, one step program per window step, the flash
    calls following the depths, and the trace readers giving what the
    harness printed on the chip."""
    rec, tr, side = chip
    assert all(n.startswith("bench.") for *_, n in tr["host"])
    assert spans.window(rec)["spans"]["engine.train_step"]
    s = rec["trace"]
    depths = [st["depth"] for st in rec["steps"]]
    assert {g[0] for g in s["idle_gaps"]} <= {
        "bench.get_batch", "bench.train_step", "bench.wait", "no host span"}
    assert len(s["step_device_s"][0]) == len(depths)
    k = s["kernels"]
    assert k["flash_fwd_lse"]["calls"] == 2 * sum(depths)
    assert k["flash_dq"]["calls"] == k["flash_dkv"]["calls"] == sum(depths)
    printed = side["result"]["metrics"]
    got = {"step_device_ms.full": readers.step_device_ms(rec, 2),
           "step_device_ms.trunc": readers.step_device_ms(rec, 1),
           "flash_attention_roofline": readers.roofline_share(rec, "flash_")}
    for name, v in got.items():
        assert v == pytest.approx(printed[name]["value"]), name


def test_the_span_names_are_the_programs():
    harness.import_program(registry.ROOT)
    from repro import obs
    assert set(spans.NAMES) == set(obs.SPANS)


def test_a_trace_without_the_programs_spans_reads_nothing(tmp_path):
    old = DATA / "yi6b_2l.spb_k2.xplane.pb"      # recorded before them
    for name in NEW:
        assert _read(name, {"trace_path": str(old)}) is None
    assert _read("gc_ms", {"trace_path": str(tmp_path)}) is None


def test_host_metrics_on_a_cpu_trace(tmp_path):
    """The harness's own window, traced on the CPU at a tiny size, read
    through ``rec["trace_path"]``: the host-side metrics, and nothing for
    the device's idle time (no TPU plane)."""
    import jax
    harness.import_program(registry.ROOT)
    root = tiny_cells.make_root(tmp_path / "root", "yi6b_2l.spb_k2")
    cell = registry.cell("yi6b_2l.spb_k2", root)
    devices = jax.devices()[:1]
    sess = harness.Session(cell, 2**31 + 11, devices)
    first = harness.n_first_steps(sess)
    for s in range(first):
        sess.step(s, sess.pipe.get_batch(s))
    with jax.profiler.trace(str(tmp_path / "trace")):
        win = harness.window(sess, first, 0.3, devices, True)
    sess.close()
    rec = {"trace_path": str(tmp_path / "trace"), "steps": win["steps"]}
    got = {name: _read(name, rec) for name in NEW}
    assert got["data_idle_ms"] is None
    assert all(isinstance(got[n], (int, float)) for n in HOST_ONLY), got
    assert got["compiles_in_window"] == 0
    assert 0 < got["data_sample_ms"] <= statistics.median(
        s["data_s"] for s in win["steps"]) * 1e3
    assert 0 < got["engine_host_ms"] <= statistics.median(
        s["dispatch_s"] for s in win["steps"]) * 1e3
    w = spans.window(rec)
    assert sorted(s[3]["step"] for s in w["spans"]["engine.train_step"]) \
        == list(range(first, first + len(win["steps"])))
