"""A training step that returns its state unchanged comes out not
correct, in every cell (tiny size, CPU)."""
import pytest

from perfbench.tests._faults import CELLS, run_tiny


@pytest.mark.parametrize("workload", CELLS)
def test_state_left_unchanged(workload, tmp_path, monkeypatch):
    res = run_tiny(tmp_path, workload, "state_unchanged", monkeypatch)
    assert res["correct"] is False
    assert res["checks"]["update_gap"]["value"] > 0.99
