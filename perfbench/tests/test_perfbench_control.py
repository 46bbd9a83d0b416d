"""The control: the reference put in the program's place, every matmul
operand rounded to float8 e4m3 (the precision below the configuration's
bfloat16), comes out not correct in every cell (tiny size, CPU)."""
import pytest

from perfbench import harness, reference
from perfbench.tests._faults import CELLS, run_tiny


@pytest.mark.parametrize("workload", CELLS)
def test_control_fails(workload, tmp_path, monkeypatch):
    program = harness.first_steps

    def control(sess):
        out = program(sess)
        low = harness.reference_readings(
            sess.hf, sess.job, sess.seed, out["batches"], out["depths"],
            reference.Variant(low="float8_e4m3fn"))
        out.update(loss=low["loss"], grad=low["grad"], change=low["change"])
        return out

    monkeypatch.setattr(harness, "first_steps", control)
    res = run_tiny(tmp_path, workload)
    assert res["correct"] is False
