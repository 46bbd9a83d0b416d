"""Depth policies: who decides "how much backprop this iteration".

The paper's cluster-level gains come from treating the per-iteration
backprop depth as a first-class, scheduler-controlled knob.  A
:class:`DepthPolicy` is the pluggable owner of that knob inside an
:class:`~repro.engine.SPBEngine` session:

* :class:`CyclePolicy` — the repo's existing temporal schedule
  (``core/spb.py``'s :class:`TemporalSchedule`: k-cycle, warmup,
  straggler rebalance), now behind the protocol.
* :class:`CostModelPolicy` — consumes ``jigsaw/costmodel.py`` estimates:
  given a per-iteration time budget (fraction of a full step), keep only
  the snapped depths whose estimated task time fits, and cycle over them.
  The deepest level is always retained so every layer keeps training.
* :class:`SchedulerHookPolicy` — a JobSpec-level controller (a JigSaw
  scheduler, a DL2-style learned policy, an HFTA fusion manager) sets the
  next-iteration depth externally via :meth:`request_depth` /
  :meth:`request_fraction`; this is the bridge from the ``jigsaw/``
  scheduling layer to real execution.

Policies emit *suffix depths over the combined enc+dec stack* (``None``
means full backprop); the engine snaps them to compiled-table entries.
"""
from __future__ import annotations

import math
from typing import Optional, Protocol, Sequence, runtime_checkable

# depth_to_bwd_stages is re-exported here because it IS the
# policy->execution mapping: a DepthPolicy's suffix depth becomes the
# pipeline truncation point (number of live suffix stages).  The
# implementation lives in repro.config so the compiled steps
# (dist/steps.py, which cannot import engine/) share the same snapping.
from repro.config import (ModelConfig, SPBConfig,  # noqa: F401
                          depth_to_bwd_stages, snap_depth, total_layers)
from repro.core import spb as spb_lib


@runtime_checkable
class DepthPolicy(Protocol):
    """Decides the SPB suffix depth for each training step."""

    def depth_for_step(self, step: int) -> Optional[int]:
        """Suffix depth for ``step`` (None = full backprop)."""
        ...

    def observe(self, step: int, step_time_s: float) -> None:
        """Optional feedback after a step.  The time is true wall-clock
        only if the policy sets ``needs_step_time = True`` (the engine
        then blocks on the step's outputs before measuring); otherwise,
        on async backends it is merely dispatch time."""
        ...


class _ObserveMixin:
    needs_step_time = False     # set True to make the engine block for
                                # real wall-clock before observe()

    def observe(self, step: int, step_time_s: float) -> None:  # noqa: D401
        pass


class FullBackpropPolicy(_ObserveMixin):
    """Always full backprop (SPB off / spatial, where the compiled step
    itself owns the per-worker depths)."""

    def depth_for_step(self, step: int) -> Optional[int]:
        return None


class CyclePolicy(_ObserveMixin):
    """The temporal k-cycle with warmup, backed by TemporalSchedule.

    The deepest level leads the cycle so every layer trains from step 0:

    >>> from repro.config import SPBConfig
    >>> from repro.configs import reduced_config
    >>> pol = CyclePolicy(reduced_config("yi-6b"),
    ...                   SPBConfig(mode="temporal", k=2))
    >>> [pol.depth_for_step(s) for s in range(4)]
    [4, 2, 4, 2]
    """

    def __init__(self, cfg: ModelConfig, spb: SPBConfig,
                 schedule: Optional[spb_lib.TemporalSchedule] = None):
        self.cfg = cfg
        self.spb = spb
        self.schedule = schedule or spb_lib.make_schedule(cfg, spb)

    def depth_for_step(self, step: int) -> Optional[int]:
        return self.schedule.depth_at(step)

    def rebalance(self, slow_positions: Sequence[int]) -> None:
        """Move the deepest cycle positions off observed-slow slots."""
        self.schedule = self.schedule.rebalance(slow_positions)


class CostModelPolicy(_ObserveMixin):
    """Budget-driven depth selection from jigsaw cost-model estimates.

    ``profile`` is a :class:`repro.jigsaw.costmodel.ModelProfile` (paper
    V100 table or HLO-derived); a step at suffix depth d is estimated as
    ``profile.task_time(d / L)``.  The policy keeps the snapped depths
    whose estimate fits ``time_budget_frac * task_time(1.0)`` — plus the
    deepest snapped depth unconditionally, so every layer still receives
    updates — and cycles over the kept set.
    """

    def __init__(self, cfg: ModelConfig, spb: SPBConfig, profile,
                 time_budget_frac: float = 0.75, warmup_steps: int = 0):
        if not 0.0 < time_budget_frac <= 1.0:
            raise ValueError(f"time_budget_frac must be in (0, 1], got "
                             f"{time_budget_frac}")
        self.cfg = cfg
        self.spb = spb
        self.profile = profile
        self.time_budget_frac = time_budget_frac
        L = total_layers(cfg)
        budget = time_budget_frac * profile.task_time(1.0)
        depths = sorted(set(spb_lib.snapped_depths(cfg, spb)))
        kept = [d for d in depths if profile.task_time(d / L) <= budget]
        deepest = depths[-1]
        if deepest not in kept:
            kept.append(deepest)
        self.depths = tuple(kept)
        self.schedule = spb_lib.TemporalSchedule(self.depths,
                                                 warmup_steps=warmup_steps)

    def depth_for_step(self, step: int) -> Optional[int]:
        return self.schedule.depth_at(step)


class SchedulerHookPolicy(_ObserveMixin):
    """External depth control: the JobSpec-level scheduler calls
    :meth:`request_depth` (or :meth:`request_fraction` with the paper's
    per-worker backprop fraction) and the engine executes that depth on
    the next iteration.  Requests are sticky until replaced; with no
    request the policy falls back to ``default`` (full backprop unless a
    fallback schedule is given).

    >>> from repro.config import SPBConfig
    >>> from repro.configs import reduced_config
    >>> hook = SchedulerHookPolicy(reduced_config("yi-6b"),
    ...                            SPBConfig(mode="temporal", k=2))
    >>> hook.depth_for_step(0) is None       # no request: full backprop
    True
    >>> hook.request_fraction(0.5)           # worker 1 of 2 -> 2 layers
    2
    >>> hook.depth_for_step(1)               # sticky until replaced
    2
    """

    def __init__(self, cfg: ModelConfig, spb: SPBConfig,
                 default: Optional[DepthPolicy] = None):
        self.cfg = cfg
        self.spb = spb
        self.default = default
        self._requested: Optional[int] = None
        self._has_request = False

    def request_depth(self, depth: Optional[int]) -> Optional[int]:
        """Set the next-iteration suffix depth (None = full backprop).
        Returns the snapped depth that will actually run."""
        if depth is not None:
            depth = snap_depth(self.cfg, depth)
        self._requested = depth
        self._has_request = True
        return depth

    def request_fraction(self, fraction: float) -> Optional[int]:
        """Paper-style request: backprop ``fraction`` of the layers
        (worker j of k requests (j+1)/k — see jigsaw/trace.py)."""
        L = total_layers(self.cfg)
        return self.request_depth(max(1, math.ceil(fraction * L)))

    def clear(self) -> None:
        self._requested = None
        self._has_request = False

    def depth_for_step(self, step: int) -> Optional[int]:
        if self._has_request:
            return self._requested
        if self.default is not None:
            return self.default.depth_for_step(step)
        return None

    def observe(self, step: int, step_time_s: float) -> None:
        if self.default is not None:
            self.default.observe(step, step_time_s)


def make_policy(name: str, cfg: ModelConfig, spb: SPBConfig, *,
                profile=None, time_budget_frac: float = 0.75) -> DepthPolicy:
    """CLI-level factory.  'cycle' | 'costmodel' | 'hook' | 'full'."""
    if spb.mode in ("off", "spatial", "temporal-mb") or name == "full":
        # depth lives inside the compiled step (or there is none to pick)
        return FullBackpropPolicy()
    if name == "cycle":
        return CyclePolicy(cfg, spb)
    if name == "costmodel":
        if profile is None:
            from repro.analysis.roofline import DRYRUN_DEVICE_KIND
            from repro.jigsaw.costmodel import profile_db
            db = profile_db(DRYRUN_DEVICE_KIND)
            profile = db.get(cfg.name)
            if profile is None:
                # no HLO-derived profile for this arch (run the dry-run to
                # produce one); a paper V100 profile keeps the policy
                # usable but its fwd:bwd ratio is not this model's
                import warnings
                profile = db["resnet50"]
                warnings.warn(
                    f"no cost-model profile for {cfg.name!r}; falling back "
                    f"to the paper's resnet50 V100 profile — run "
                    f"launch/dryrun.py to derive a real one", stacklevel=2)
        return CostModelPolicy(cfg, spb, profile,
                               time_budget_frac=time_budget_frac,
                               warmup_steps=spb.warmup_steps)
    if name == "hook":
        return SchedulerHookPolicy(cfg, spb, default=CyclePolicy(cfg, spb))
    raise ValueError(f"unknown depth policy {name!r}; "
                     f"known: cycle, costmodel, hook, full")
