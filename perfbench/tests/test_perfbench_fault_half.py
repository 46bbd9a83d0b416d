"""A step that leaves out half of the batch, taking the mean over the
rest, comes out not correct, in every cell (tiny size, CPU)."""
import pytest

from perfbench.tests._faults import CELLS, run_tiny


@pytest.mark.parametrize("workload", CELLS)
def test_half_batch_left_out(workload, tmp_path, monkeypatch):
    from repro.models import lm
    whole = lm.loss_fn

    def half(params, batch, cfg, **kw):
        n = batch["tokens"].shape[0] // 2
        return whole(params, {k: v[:n] for k, v in batch.items()}, cfg, **kw)

    monkeypatch.setattr(lm, "loss_fn", half)
    res = run_tiny(tmp_path, workload)
    assert res["correct"] is False
