"""The comparison's bookkeeping: every number compared has its limit, and
a configuration that states what the trainer does not run is refused."""
import json

import pytest

from perfbench import harness, registry

NUMBERS = {k: {"value": 1e-3, "at": "x"} for k in harness.NUMBERS}


def test_judge_needs_every_limit():
    full = {"loss_gap": 1e-2, "grad_gap": 1e-2, "update_gap": 1e-2}
    assert set(harness.judge(NUMBERS, full)) == set(harness.NUMBERS)
    for k in full:
        with pytest.raises(ValueError):
            harness.judge(NUMBERS, {j: v for j, v in full.items() if j != k})
    with pytest.raises(ValueError):
        harness.judge(NUMBERS, dict(full, loss_gaps=1e-2))


def test_float32_residual_is_refused():
    # Mamba-2's published config keeps the residual in float32; the
    # trainer has no such option, so the file cannot claim it
    harness.import_program(registry.ROOT)
    hf = registry.canonical(json.loads(
        (registry.ROOT / "perfbench/configs/mamba2_12l.json").read_text()))
    assert hf["residual_in_fp32"] is True
    with pytest.raises(ValueError, match="residual_in_fp32"):
        harness.program_config(hf)
    harness.program_config(dict(hf, residual_in_fp32=False))
