"""A step that leaves out half of the batch, taking the mean over the
rest, comes out not correct, in every cell (tiny size, CPU)."""
import pytest

from perfbench.tests._faults import CELLS, run_tiny


@pytest.mark.parametrize("workload", CELLS)
def test_half_batch_left_out(workload, tmp_path, monkeypatch):
    res = run_tiny(tmp_path, workload, "half_batch", monkeypatch)
    assert res["correct"] is False
