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


class _Trainer:
    """The trainer's config with a ``residual_in_fp32`` option of its own,
    or (``option`` None) with none, whatever the real config holds."""

    def __init__(self, cfg, option):
        self._cfg, self._option = cfg, option

    def __getattr__(self, name):
        if name != "residual_in_fp32":
            return getattr(self._cfg, name)
        if self._option is None:
            raise AttributeError(name)
        return self._option


@pytest.mark.parametrize("config", ["mamba2_12l", "yi6b_2l"])
@pytest.mark.parametrize("option", [True, False, None])
def test_residual_in_fp32_read_from_the_trainer(config, option, monkeypatch):
    # a trainer without the option keeps the residual in its compute
    # dtype, so a file that states float32 (Mamba-2's) is refused there
    harness.import_program(registry.ROOT)
    import repro.configs as configs
    real = configs.get_config

    class Arch:
        def __init__(self, arch):
            self.arch = arch

        def scaled(self, **kw):
            return _Trainer(real(self.arch).scaled(**kw), option)

    monkeypatch.setattr(configs, "get_config", Arch)
    hf = registry.canonical(json.loads(
        (registry.ROOT / f"perfbench/configs/{config}.json").read_text()))
    runs = bool(option)
    if runs == hf.get("residual_in_fp32", False):
        cfg = harness.program_config(hf)
        assert getattr(cfg, "residual_in_fp32", False) is runs
    else:
        with pytest.raises(ValueError, match="residual_in_fp32"):
            harness.program_config(hf)


def test_mesh_from_the_job():
    import jax
    one = jax.devices()[:1]
    spmd = harness.make_mesh({"parallelism": "spmd"}, one)
    assert dict(spmd.shape) == {"data": 1, "model": 1}
    assert harness.stage_of(spmd) is None
    with pytest.raises(ValueError, match="does not hold"):
        harness.make_mesh({"parallelism": "pipeline",
                           "mesh": {"stage": 2, "data": 1, "model": 2}}, one)
