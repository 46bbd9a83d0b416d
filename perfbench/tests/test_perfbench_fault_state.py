"""A training step that returns its state unchanged comes out not
correct, in every cell (tiny size, CPU)."""
import pytest

from perfbench.tests._faults import CELLS, run_tiny


@pytest.mark.parametrize("workload", CELLS)
def test_state_left_unchanged(workload, tmp_path, monkeypatch):
    import jax.numpy as jnp
    from repro.optim import optimizers

    def unchanged(params, grads, opt_state, step, tcfg, **kw):
        return params, opt_state, {"grad_norm": jnp.zeros(()),
                                   "lr": jnp.zeros(())}

    monkeypatch.setattr(optimizers, "apply_updates", unchanged)
    res = run_tiny(tmp_path, workload)
    assert res["correct"] is False
    assert res["checks"]["update_gap"]["value"] > 0.99
