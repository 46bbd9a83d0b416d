"""The pipeline cells: the two ``pp.*`` readers on hand-built device
intervals, and a tiny stand-in of the four-chip cell (2 stages x 2-way
tensor parallel, 4 microbatches) run whole on four host CPU devices."""
import pytest

from perfbench import harness, readers, registry, tracefile
from perfbench.tests._faults import MULTI, run_tiny

FUSION = "%fusion.1 = bf16[2,64]{1,0} fusion(bf16[2,64]{1,0} %p)"
START = ("%all-reduce-start.1 = f32[64]{0} all-reduce-start(f32[64]{0} "
         "%fusion.1), replica_groups={{0,1}}")
DONE = "%all-reduce-done.1 = f32[64]{0} all-reduce-done(f32[64]{0} %s)"
PERMUTE = ("%collective-permute-done.2 = bf16[2,64]{1,0} "
           "collective-permute-done((bf16[2,64]{1,0}, bf16[2,64]{1,0}) %c)")


def _device(ops, flight=(), modules=((0, 100),)):
    return {"ops": list(ops), "async": list(flight),
            "modules": [(a, b, f"jit_step({i})") for i, (a, b) in
                        enumerate(modules)]}


def _rec(devices, stages):
    trace = {"devices": {f"/device:TPU:{i}": d for i, d in
                         enumerate(devices)}, "host": []}
    hi = max(b for d in devices for _, b, _ in d["modules"])
    depths = [8] * len(devices[0]["modules"])
    return {"trace": tracefile.summarize(trace, 0, hi, depths),
            "stage_of": {i: s for i, s in enumerate(stages)}}


def _read(name, rec):
    return registry.reader(name)(rec)


def test_collectives_by_name():
    for name in (START, DONE, PERMUTE, "%all-gather.3 = bf16[4]{0} "
                 "all-gather(bf16[2]{0} %x)",
                 "%reduce-scatter.1 = f32[2]{0} reduce-scatter(f32[4] %x)",
                 "%all-to-all = f32[2]{0} all-to-all(f32[2] %x)"):
        assert tracefile.is_collective(name), name
    assert not tracefile.is_collective(FUSION)
    assert not tracefile.is_collective(
        "%copy-start.3 = (bf16[2], bf16[2], u32[]) copy-start(bf16[2] %x)")


def test_a_known_gap_is_the_bubble():
    # one device, one 100 ns step: ops 0-40 and 60-100, nothing in 40-60
    dev = _device([(0, 40, FUSION), (60, 100, FUSION)])
    rec = _rec([dev], [0])
    assert _read("pp.bubble_share", rec) == pytest.approx(20.0)
    assert _read("pp.exposed_collective_share", rec) == 0.0


def test_a_collective_half_overlapped_by_compute():
    # compute 0-25 and 26-50; an all-reduce started in 25-26 is in flight
    # until 75, its done op waiting in 70-75: 26-50 runs under compute,
    # the start op's own 1 ns and 50-75 are exposed; 75-100 is idle
    dev = _device([(0, 25, FUSION), (25, 26, START), (26, 50, FUSION),
                   (70, 75, DONE)], [(25, 75, START)])
    rec = _rec([dev], [0])
    assert _read("pp.exposed_collective_share", rec) == pytest.approx(26.0)
    assert _read("pp.bubble_share", rec) == pytest.approx(25.0)


def test_nested_ops_run_only_in_their_own_time():
    # a conditional holds a fusion and a permute's wait: the wait is
    # exposed though its parent spans it
    cond = "%conditional.1 = bf16[2,64]{1,0} conditional(s32[] %i)"
    dev = _device([(0, 100, cond), (0, 60, FUSION), (60, 90, PERMUTE)])
    rec = _rec([dev], [0])
    assert _read("pp.exposed_collective_share", rec) == pytest.approx(30.0)
    assert _read("pp.bubble_share", rec) == 0.0


def test_four_devices_and_their_stages():
    # two steps of 100 ns on each device; stage 0 (devices 0, 2) idles 30
    # ns a step, stage 1 (1, 3) waits 10 ns a step on a permute
    mods = ((0, 100), (100, 200))
    s0 = _device([(0, 70, FUSION), (100, 170, FUSION)], modules=mods)
    s1 = _device([(0, 90, FUSION), (90, 100, PERMUTE), (100, 190, FUSION),
                  (190, 200, PERMUTE)], modules=mods)
    rec = _rec([s0, s1, s0, s1], [0, 1, 0, 1])
    assert _read("pp.bubble_share", rec) == pytest.approx(15.0)
    assert _read("pp.exposed_collective_share", rec) == pytest.approx(5.0)
    split = dict((n, v * 1e9) for n, v in readers.stage_split(rec))
    assert split == pytest.approx({
        "stage 0 module": 200, "stage 0 idle": 60,
        "stage 0 exposed collective": 0, "stage 1 module": 200,
        "stage 1 idle": 0, "stage 1 exposed collective": 20})


def test_a_cell_without_stages_reads_nothing():
    rec = _rec([_device([(0, 40, FUSION)])], [0])
    rec["stage_of"] = None
    assert _read("pp.bubble_share", rec) is None
    assert _read("pp.exposed_collective_share", rec) is None
    assert readers.stage_split(rec) is None


@pytest.mark.parametrize("workload", MULTI)
def test_tiny_pipeline_cell_is_correct(workload, tmp_path):
    res = run_tiny(tmp_path, workload)
    cell = registry.cell(workload)
    assert res["correct"] is True, res["checks"]
    assert res["device"]["count"] == cell.chips
    # the whole depth cycle, at least three steps (yi6b_8l k=2: 8, 4, 8)
    cycle = harness.cycle_depths(cell.job, cell.hf)
    depths = res["setup"]["first_steps_depths"]
    assert sorted(set(depths)) == sorted(cycle)
    assert len(depths) == max(len(cycle), harness.CHECK_STEPS)
    assert all(c["value"] <= c["limit"] for c in res["checks"].values())
