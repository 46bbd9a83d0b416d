"""A sound tiny run of every one-chip cell comes out correct (CPU), so
that the fault tests' "not correct" is the fault's doing."""
import pytest

from perfbench import registry
from perfbench.tests._faults import run_tiny

ONE_CHIP = [w["name"] for w in registry.benchmark()["workloads"]
            if w["chips"] == 1]


@pytest.mark.parametrize("workload", ONE_CHIP)
def test_tiny_cell_is_correct(workload, tmp_path):
    res = run_tiny(tmp_path, workload)
    assert res["correct"] is True, res["checks"]
    assert all(c["value"] <= c["limit"] for c in res["checks"].values())
