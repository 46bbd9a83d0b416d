"""The control: the reference put in the program's place, every matmul
operand rounded to float8 e4m3 (the precision below the configuration's
bfloat16), comes out not correct in every cell (tiny size, CPU)."""
import pytest

from perfbench.tests._faults import CELLS, run_tiny


@pytest.mark.parametrize("workload", CELLS)
def test_control_fails(workload, tmp_path, monkeypatch):
    res = run_tiny(tmp_path, workload, "control", monkeypatch)
    assert res["correct"] is False
