"""A step that leaves out an exchange between chips comes out not
correct, in every cell on several chips (tiny size, CPU devices): the
pipeline's sends between stages, and the tensor-parallel all-reduce."""
import pytest

from perfbench.tests._faults import MULTI, run_tiny


@pytest.mark.parametrize("fault", ["stage_exchange", "tensor_exchange"])
@pytest.mark.parametrize("workload", MULTI)
def test_exchange_left_out(workload, fault, tmp_path):
    res = run_tiny(tmp_path, workload, fault)
    assert res["correct"] is False
