"""The trace reduction on small traces recorded on a TPU v5e through the
harness (``harness.run`` with ``trace=True``), committed under ``data/``:
yi-6b and Mamba-2 cells with every width shrunk (2 and 12 layers, the
cells' SPB cycles), a window of a few steps."""
import json
from pathlib import Path

import pytest

from perfbench import tracefile

DATA = Path(__file__).resolve().parent / "data"
#: The depth of each traced window step: the window starts after the job's
#: first max(k, 3) steps, and the trainer's cycle interleaves deep and
#: shallow depths (yi k=2: 2, 1, 2, ...; mamba2 k=4: 12, 3, 9, 6, ...).
CYCLES = {"yi6b_2l.spb_k2": ([2, 1], 3, 2),
          "mamba2_12l.spb_k4": ([12, 3, 9, 6], 4, 12)}


def _load(name):
    tr = tracefile.load(str(DATA / f"{name}.xplane.pb"))
    spans = [(a, b) for a, b, n in tr["host"] if n == "bench.window"]
    assert len(spans) == 1
    steps = sum(1 for *_, n in tr["host"] if n == "bench.train_step")
    cycle, first, n_layers = CYCLES[name]
    depths = [cycle[(first + i) % len(cycle)] for i in range(steps)]
    return tr, spans[0], depths, n_layers


@pytest.mark.parametrize("name", sorted(CYCLES))
def test_window_busy_and_steps(name):
    tr, (lo, hi), depths, _ = _load(name)
    s = tracefile.summarize(tr, lo, hi, depths)
    assert s["devices"] == 1
    assert 0 < s["busy_s"] <= s["window_s"] == (hi - lo) / 1e9
    # one step program per window step, each inside the window
    assert len(s["step_device_s"][0]) == len(depths) >= 2
    assert all(0 < t < s["window_s"] for t in s["step_device_s"][0])
    labels = {"bench.get_batch", "bench.train_step", "bench.wait",
              "no host span"}
    assert {g[0] for g in s["idle_gaps"]} <= labels
    assert all(g[1] > 0 for g in s["idle_gaps"])
    assert all(op[0].split(":")[0] in {f"d{d}" for d in depths}
               for op in s["device_ops"])


def test_flash_attention_calls_follow_the_depths():
    tr, (lo, hi), depths, n_layers = _load("yi6b_2l.spb_k2")
    k = tracefile.summarize(tr, lo, hi, depths)["kernels"]
    trained = sum(depths)
    frozen = n_layers * len(depths) - trained
    # frozen layers run the plain forward; trained ones the forward that
    # keeps the logsumexp, twice under remat, and one dq and one dkv
    assert k.get("flash_fwd", {"calls": 0})["calls"] == frozen
    assert k["flash_fwd_lse"]["calls"] == 2 * trained
    assert k["flash_dq"]["calls"] == k["flash_dkv"]["calls"] == trained
    assert not any(kind.startswith("ssd") for kind in k)


def test_ssd_calls_follow_the_depths():
    tr, (lo, hi), depths, n_layers = _load("mamba2_12l.spb_k4")
    k = tracefile.summarize(tr, lo, hi, depths)["kernels"]
    trained = sum(depths)
    frozen = n_layers * len(depths) - trained
    # frozen layers run the plain forward; trained ones the forward that
    # keeps chunk states, twice under remat, and one backward
    assert k.get("ssd_fwd", {"calls": 0})["calls"] == frozen
    assert k["ssd_fwd_states"]["calls"] == 2 * trained
    assert k["ssd_bwd"]["calls"] == trained
    assert not any(kind.startswith("flash") for kind in k)


def test_helpers():
    assert tracefile.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert tracefile.gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]
    nested = [(0, 10, "%while.1 = f32[] while()"),
              (2, 4, "%fusion.1 = bf16[2] fusion()")]
    assert [t[3] for t in tracefile.self_times(nested)] == [8, 2]
    assert tracefile.kernel_kind(
        "%_ssd_jit.3 = (f32[8,64,64]{2,1,0}, f32[8,64,128]{2,1,0}) "
        "custom-call(bf16[8,64,64]{2,1,0} %a)") == "ssd_fwd"
    assert tracefile.kernel_kind("%fusion.2 = f32[2] fusion()") is None


#: flash_attention_roofline and mfu of the two stored yi6b_2l traces, read
#: with the cell's record before kernel calls were priced at the shape of
#: one device's microbatch (the widths of the spans trace's sidecar; the
#: window's length from the trace).
BEFORE = {"yi6b_2l.spb_k2": (18.060357973898288, 0.467217366558213),
          "yi6b_2l.spb_k2.spans": (18.146685505537654, 1.2400137849229567)}


@pytest.mark.parametrize("name", sorted(BEFORE))
@pytest.mark.parametrize("mesh", [None, {"stage": 1, "data": 1, "model": 1}])
def test_one_chip_readings_unchanged_by_the_call_shape(name, mesh):
    import json

    from perfbench import counts, registry
    side = json.loads((DATA / "yi6b_2l.spb_k2.spans.json").read_text())
    job = dict(side["job"], **({"mesh": mesh} if mesh else {}))
    tr = tracefile.load(str(DATA / f"{name}.xplane.pb"))
    (lo, hi), = [(a, b) for a, b, n in tr["host"] if n == "bench.window"]
    n = sum(1 for *_, nm in tr["host"] if nm == "bench.train_step")
    depths = [[2, 1][(3 + i) % 2] for i in range(n)]
    s = tracefile.summarize(tr, lo, hi, depths)
    rec = {"hf": side["hf"], "job": job, "peaks": side["peaks"], "chips": 1,
           "steps": [{"depth": d} for d in depths], "trace": s,
           "window_s": s["window_s"],
           "flops": {d: counts.step_flops(side["hf"], job, d)
                     for d in set(depths)}}
    flash, mfu = BEFORE[name]
    assert registry.reader("flash_attention_roofline")(rec) == flash
    assert registry.reader("mfu")(rec) == mfu


KERNELS = json.loads((DATA / "readings.json").read_text())["trace_kernels"]


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_calls_unchanged(name):
    """The Pallas calls found in each stored trace, by kind, with their
    device seconds, equal those recorded before the kernel families moved
    into files of their own."""
    tr = tracefile.load(str(DATA / f"{name}.xplane.pb"))
    (lo, hi), = [(a, b) for a, b, n in tr["host"] if n == "bench.window"]
    n = sum(1 for *_, nm in tr["host"] if nm == "bench.train_step")
    cycle, first, _ = CYCLES[name.removesuffix(".spans")]
    depths = [cycle[(first + i) % len(cycle)] for i in range(n)]
    assert tracefile.summarize(tr, lo, hi, depths)["kernels"] == \
        KERNELS[name]
