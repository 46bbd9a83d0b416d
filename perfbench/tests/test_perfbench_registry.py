"""Every cell of BENCHMARK.json resolves to its files, and a configuration,
a traffic mix, a cell and a per-layer metric added as new files are
found without editing any file that is there."""
import json
import shutil

import pytest

from perfbench import harness, registry

BENCH = registry.benchmark()
NAMES = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("name", NAMES)
def test_cell_resolves(name):
    cell = registry.cell(name)
    assert cell.hf["num_hidden_layers"] >= 1 and cell.job["batch"] >= 1
    assert set(cell.limits["limits"]) == set(harness.NUMBERS)
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in cell.per_layer:
        assert callable(registry.reader(m["name"]))


def test_contract_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    configs = {c["name"] for c in BENCH["configs"]}
    assert configs == {w["config"] for w in BENCH["workloads"]}
    moves = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in moves
        assert set(m.get("workloads", NAMES)) <= set(NAMES)
    for c in BENCH["configs"]:
        hf = json.loads((registry.ROOT / c["file"]).read_text())
        assert hf["reduced"] == c["reduced"]


def test_new_files_are_found(tmp_path):
    root = tmp_path
    shutil.copytree(registry.ROOT / "perfbench", root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads(json.dumps(BENCH))
    old = bench["configs"][0]
    hf = json.loads((registry.ROOT / old["file"]).read_text())
    hf["name"] = "added_cfg"
    (root / "perfbench/configs/added_cfg.json").write_text(json.dumps(hf))
    job = registry.cell(NAMES[0]).job
    (root / "perfbench/traffic/added_mix.json").write_text(
        json.dumps(dict(job, batch=1)))
    (root / "perfbench/limits/added_cfg.added_mix.json").write_text(
        json.dumps({"limits": {"loss_gap": 1, "grad_gap": 1,
                               "update_gap": 1}}))
    (root / "perfbench/metrics/added_metric.py").write_text(
        "def read(rec):\n    return 42.0\n")
    bench["configs"].append(dict(old, name="added_cfg",
                                 file="perfbench/configs/added_cfg.json"))
    bench["workloads"].append({"name": "added_cfg.added_mix",
                               "config": "added_cfg", "traffic": "added_mix",
                               "chips": 1, "why": "added"})
    bench["per_layer"].append({"name": "added_metric", "unit": "ms",
                               "better": "lower", "source": "host_clock",
                               "layer": "engine", "moves": "tokens_per_s",
                               "workloads": ["added_cfg.added_mix"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = registry.cell("added_cfg.added_mix", root)
    assert cell.hf["name"] == "added_cfg" and cell.job["batch"] == 1
    assert [m["name"] for m in cell.per_layer] == ["added_metric"]
    assert registry.reader("added_metric", root)({}) == 42.0
    with pytest.raises(KeyError):
        registry.cell("no_such_cell", root)
