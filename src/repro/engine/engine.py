"""SPBEngine: one training-session object behind every entry point.

Before this package, each consumer (train driver, dry-run, benchmark,
example) hand-wired the same pipeline — build per-depth step functions,
jit them, recompute state shapes, pick a depth per step — with slightly
different (and drifting) choices: the trainer disabled donation, the
dry-run recomputed state shapes per depth, the benchmark bypassed
sharding entirely.  ``SPBEngine`` owns that pipeline once:

* **mesh + params + optimizer state** — the session owns the train state;
  entry points never touch placement.
* **a pluggable DepthPolicy** — the paper's "how much backprop this
  iteration" knob (cycle schedule, cost-model budget, or an external
  JobSpec-level scheduler via the hook policy).
* **a compiled per-depth step table with real signatures** — jit'd with
  ``in_shardings``/``out_shardings`` + ``donate_argnums=(0,)`` so params
  and optimizer state update in place (the old path pinned layouts with
  in-function constraints and ran with ``donate=False``).
* **AOT lower/compile + export/import** — the table serializes to disk
  (``engine/aot.py``) and a fresh process reloads it without re-tracing,
  so dry-run artifacts and the trainer share one cache.

The per-depth step *functions* are unchanged — ``dist/steps.py`` remains
the engine's internals; this module owns their compilation and lifecycle.
"""
from __future__ import annotations

import hashlib
import json
from pathlib import Path
from typing import Any, Callable, Dict, Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

import dataclasses

from repro import obs
from repro.config import (ModelConfig, SPBConfig, TrainConfig, snap_depth,
                          snap_depth_to_stages)
from repro.dist import sharding as shd
from repro.dist import steps as steps_lib
from repro.engine import aot, stepcache
from repro.engine.policies import DepthPolicy, make_policy
from repro.launch.mesh import make_host_mesh, parallel_config_for

State = Dict[str, Any]


class SPBEngine:
    """A training session: mesh + state + depth policy + step table.

    Constructing a session builds the per-depth step table (tracing and
    compilation stay lazy) and derives state shapes/shardings once:

    >>> from repro.config import SPBConfig, TrainConfig
    >>> from repro.configs import reduced_config
    >>> engine = SPBEngine(reduced_config("yi-6b"), TrainConfig(),
    ...                    SPBConfig(mode="temporal", k=2))
    >>> engine.depth_keys()           # full backprop + the k-cycle depths
    [None, 2, 4]
    >>> engine.resolve_depth(3)       # depths snap UP, never less backprop
    3

    Typical use::

        engine.init_state(jax.random.key(0))
        for step in range(tcfg.num_steps):
            metrics = engine.train_step(pipe.get_batch(step), step)

    AOT use (dry-run / cache-sharing)::

        specs = engine.batch_specs_like(sample_batch)
        engine.compile_table(specs)
        engine.export_aot(cache_dir, specs)     # other processes import

    Pipeline sessions (``parallelism="pipeline"``) run the same surface
    over a ``(stage, data[, model])`` mesh from ``launch.mesh.
    make_pipeline_mesh`` — the engine stamps ``spb.pipeline_stages`` from
    the mesh so depth policies emit stage-snapped depths, shards
    microbatches over ``data`` inside the schedule interpreter, and keys
    the AOT cache on the ``(parallelism, schedule, data, tensor, zero2)``
    extras on top of the mesh topology.  ``tensor_parallel`` (default:
    the mesh's model-axis size) column/row-shards stage weights over
    ``model`` with explicit collectives at the joins; ``tensor_parallel=
    1`` on a 3-D mesh is the replicated baseline.  ``sequence_parallel``
    shards the in-stage residual stream over ``model`` on the sequence
    dim; ``zero2`` reduce-scatters stage grads over ``data`` into the
    ZeRO-1 moments' layout.
    """

    def __init__(self, cfg: ModelConfig, tcfg: TrainConfig,
                 spb_cfg: Optional[SPBConfig] = None, *,
                 mesh=None, policy: Optional[DepthPolicy] = None,
                 donate: bool = True, zero1: bool = True,
                 parallelism: str = "spmd",
                 pipeline_schedule: str = "1f1b",
                 tensor_parallel: Optional[int] = None,
                 sequence_parallel: bool = False,
                 zero2: bool = False,
                 shared_cache: bool = True):
        if parallelism not in ("spmd", "pipeline"):
            raise ValueError(f"unknown parallelism {parallelism!r}; "
                             f"known: spmd, pipeline")
        obs.install()
        self.cfg = cfg
        self.tcfg = tcfg
        self.spb = spb_cfg or SPBConfig()
        self.parallelism = parallelism
        self.pipeline_schedule = pipeline_schedule
        if parallelism == "pipeline":
            from repro.launch.mesh import make_pipeline_mesh
            if mesh is None:
                mesh = make_pipeline_mesh()
            pcfg = parallel_config_for(mesh)
            if pcfg.pp_axis is None:
                raise ValueError("pipeline parallelism needs a mesh with a "
                                 "'stage' axis (launch.mesh."
                                 "make_pipeline_mesh)")
            self.pipeline_stages = pcfg.num_pp
            # tensor parallelism defaults to the mesh's model-axis size;
            # an explicit tensor_parallel=1 on a 3-D mesh is the
            # *replicated baseline* (the thing the HLO tests compare
            # against), so a mismatch is only an error when sharding is on
            msize = int(dict(zip(mesh.axis_names,
                                 mesh.devices.shape)).get("model", 1))
            self.tensor_parallel = (msize if tensor_parallel is None
                                    else int(tensor_parallel))
            if self.tensor_parallel > 1 and self.tensor_parallel != msize:
                raise ValueError(
                    f"tensor_parallel={self.tensor_parallel} but mesh "
                    f"{tuple(mesh.axis_names)}={tuple(mesh.devices.shape)} "
                    f"has model-axis size {msize}")
            if sequence_parallel and self.tensor_parallel <= 1:
                raise ValueError("sequence_parallel requires "
                                 "tensor_parallel > 1")
            self.sequence_parallel = bool(sequence_parallel)
            self.zero2 = bool(zero2)
            # stage-snap the whole depth machinery (schedules, policies,
            # LR-rescale contributors) to what the pipeline can freeze
            if self.spb.pipeline_stages != self.pipeline_stages:
                self.spb = dataclasses.replace(
                    self.spb, pipeline_stages=self.pipeline_stages)
            # heterogeneous stage maps (per-group unit slices) change
            # which param groups get a leading stage dim in the stacked
            # state — sharding specs need the per-group uniformity flags
            from repro.dist.pipeline import stage as pp_stage
            pp_stage.check_pipeline_compatible(cfg, self.pipeline_stages)
            self._stage_map = pp_stage.build_stage_map(
                cfg, self.pipeline_stages)
            self._uniform_groups = self._stage_map.uniform
        else:
            if tensor_parallel not in (None, 1) or sequence_parallel or zero2:
                raise ValueError("tensor_parallel / sequence_parallel / "
                                 "zero2 are pipeline-session knobs")
            if mesh is None:
                mesh = make_host_mesh()
            self.pipeline_stages = 0
            self.pipeline_data = 0
            self.tensor_parallel = 0
            self.sequence_parallel = False
            self.zero2 = False
            self._stage_map = None
            self._uniform_groups = None
        self.donate = donate
        self.zero1 = zero1
        self.shared_cache = shared_cache
        self.policy = policy or make_policy("cycle", cfg, self.spb)

        # the old dist.steps functions are the engine's internals
        if parallelism == "pipeline":
            self._raw: Dict[Any, Callable] = \
                steps_lib.build_pipeline_train_steps(
                    cfg, tcfg, self.spb, num_stages=self.pipeline_stages,
                    schedule=pipeline_schedule,
                    tensor_parallel=self.tensor_parallel,
                    sequence_parallel=self.sequence_parallel,
                    zero2=self.zero2)
        else:
            self._raw = steps_lib.build_spb_train_steps(cfg, tcfg, self.spb)

        # shapes computed exactly once for the whole session (the
        # pre-engine drivers recomputed these per depth and dropped the
        # result); mesh-dependent specs/shardings live in _bind_mesh so
        # resize() can re-derive them for a new submesh
        self.state_shapes: State = steps_lib.train_state_shapes(cfg, tcfg)
        self._bind_mesh(mesh)

        self._steps: Dict[Any, Callable] = {}      # jitted or AOT-loaded
        self._compiled: Dict[Any, Any] = {}        # AOT Compiled objects
        self._frozen = False                       # True after AOT import
        self._warned_depths: set = set()
        self.state: Optional[State] = None
        self.last_depth: Any = None
        self._auto_step = 0
        self.resizes = 0

    def _bind_mesh(self, mesh) -> None:
        """Derive everything mesh-dependent: parallel config, state/batch
        shardings.  Called from __init__ and again on every resize()."""
        self.mesh = mesh
        self.parallel = parallel_config_for(mesh)
        if self.parallelism == "pipeline":
            # the composable data axis: microbatches shard over it inside
            # the schedule interpreter, ZeRO-1 moments shard over it per
            # stage; 1 when the session mesh is stage-only
            self.pipeline_data = self.parallel.num_dp
            self.state_specs = shd.pipeline_state_pspec(
                self.state_shapes, mesh=mesh, zero1=self.zero1,
                uniform_groups=self._uniform_groups)
        else:
            self.state_specs = shd.state_pspec(
                self.state_shapes, mesh=mesh, zero1=self.zero1)
        self.state_shardings = jax.tree.map(
            lambda s: NamedSharding(mesh, s), self.state_specs,
            is_leaf=lambda x: isinstance(x, P))
        # one prefix sharding covers every batch leaf: dim 0 over the DP
        # axes, the rest replicated
        self.batch_sharding = NamedSharding(
            mesh, shd.spec_for(("batch",), mesh=mesh))
        self._metrics_sharding = NamedSharding(mesh, P())

    # -- state lifecycle ---------------------------------------------------

    def init_state(self, key) -> State:
        """Initialize and place the session's train state."""
        with jax.sharding.set_mesh(self.mesh):
            state = steps_lib.init_train_state(key, self.cfg, self.tcfg)
        return self.attach_state(state)

    def attach_state(self, state: State) -> State:
        """Adopt an externally built/restored state (re-places it)."""
        self.state = jax.device_put(state, self.state_shardings)
        return self.state

    @property
    def step_count(self) -> int:
        return int(self.state["step"]) if self.state is not None else 0

    # -- step table --------------------------------------------------------

    def depth_keys(self):
        """Keys of the session's step table."""
        seen = dict.fromkeys(list(self._raw) + list(self._steps))
        return list(seen)

    def _raw_step(self, key: Any) -> Callable:
        if key not in self._raw:
            # lazily extend the table for off-cycle depths (hook policy)
            if self.parallelism == "pipeline":
                self._raw[key] = steps_lib.make_pipeline_train_step(
                    self.cfg, self.tcfg, self.spb, depth=key,
                    num_stages=self.pipeline_stages,
                    schedule=self.pipeline_schedule,
                    tensor_parallel=self.tensor_parallel,
                    sequence_parallel=self.sequence_parallel,
                    zero2=self.zero2)
            else:
                self._raw[key] = steps_lib.make_train_step(
                    self.cfg, self.tcfg, self.spb, depth=key)
        return self._raw[key]

    def _jit(self, key: Any):
        return jax.jit(
            self._raw_step(key),
            in_shardings=(self.state_shardings, self.batch_sharding),
            out_shardings=(self.state_shardings, self._metrics_sharding),
            donate_argnums=(0,) if self.donate else ())

    def _step_signature(self) -> str:
        """Digest of everything that determines a step's compiled program
        except (depth, mesh) — the step-cache key's config component.
        Reuses the AOT key's train-config scrub, so engines differing only
        by data seed / checkpoint knobs share entries."""
        ident = aot.step_ident(self.cfg, self.tcfg, self.spb,
                               zero1=self.zero1, donate=self.donate)
        ident["parallelism"] = self.parallelism
        if self.parallelism == "pipeline":
            ident["pipeline_schedule"] = self.pipeline_schedule
            ident["tensor_parallel"] = self.tensor_parallel
            ident["sequence_parallel"] = self.sequence_parallel
            ident["zero2"] = self.zero2
        blob = json.dumps(ident, sort_keys=True, default=str).encode()
        return hashlib.sha256(blob).hexdigest()[:16]

    def step_cache_key(self, key: Any):
        """The process-wide step-cache key for one depth entry:
        (config digest, depth tag, mesh fingerprint)."""
        if not hasattr(self, "_step_sig"):
            self._step_sig = self._step_signature()
        return (self._step_sig, aot._depth_tag(key),
                stepcache.mesh_fingerprint(self.mesh))

    def step_fn(self, key: Any) -> Callable:
        """The (state, batch) -> (state, metrics) executable for a depth
        key (None = full backprop, int = suffix depth, 'mb' = cycle).

        With ``shared_cache`` (the default) the jitted wrapper comes from
        the process-wide :data:`repro.engine.stepcache.GLOBAL` table, so
        every co-located engine with the same (config, depth, submesh)
        shares one wrapper — and one trace + compile."""
        if key not in self._steps:
            if self._frozen:
                raise KeyError(
                    f"AOT step table has no entry for depth {key!r}; "
                    f"available: {sorted(map(str, self._steps))}")
            with jax.sharding.set_mesh(self.mesh):
                if self.shared_cache:
                    self._steps[key] = stepcache.GLOBAL.get_or_build(
                        self.step_cache_key(key), lambda: self._jit(key))
                else:
                    self._steps[key] = self._jit(key)
        return self._steps[key]

    # -- elastic resizing ---------------------------------------------------

    def resize(self, mesh) -> "SPBEngine":
        """Re-place this session onto a different (sub)mesh at an
        iteration boundary — the burst-parallel knob.

        Re-derives parallel config + shardings for the new mesh, reshards
        the live train state onto it (``device_put``, the same
        reshard-on-restore path checkpoint recovery uses) and drops the
        mesh-bound step entries.  Steps re-resolve through the shared
        step cache, so bouncing back to a previously-used submesh
        re-traces nothing.  An AOT-frozen table is abandoned (frozen
        executables are placement-specific); pipeline sessions can only
        resize onto a mesh with the same stage count.
        """
        if mesh is self.mesh:
            return self
        if self.parallelism == "pipeline":
            pcfg = parallel_config_for(mesh)
            if pcfg.pp_axis is None or pcfg.num_pp != self.pipeline_stages:
                raise ValueError(
                    f"pipeline session with {self.pipeline_stages} stages "
                    f"cannot resize onto mesh {tuple(mesh.axis_names)}="
                    f"{tuple(mesh.devices.shape)}")
            msize = int(dict(zip(mesh.axis_names,
                                 mesh.devices.shape)).get("model", 1))
            if self.tensor_parallel > 1 and msize != self.tensor_parallel:
                raise ValueError(
                    f"tensor-sharded session (tensor_parallel="
                    f"{self.tensor_parallel}) cannot resize onto a mesh "
                    f"with model-axis size {msize}")
        self._bind_mesh(mesh)
        self._steps = {}
        self._compiled = {}
        self._frozen = False
        self._warned_depths = set()
        if self.state is not None:
            self.attach_state(self.state)
        self.resizes += 1
        return self

    def resolve_depth(self, depth: Optional[int]) -> Any:
        """Map a policy-requested depth to a step-table key.

        Depths snap UP to unit boundaries (never less backprop).  When the
        table is frozen (AOT-imported), an absent depth resolves to the
        nearest *deeper* available entry — deeper is always convergence-
        safe — with a warning; if no deeper entry exists this is a hard
        error, because silently running full backprop instead would erase
        the SPB savings without any visible failure."""
        if depth is None:
            return None
        if self.parallelism == "pipeline":
            depth = snap_depth_to_stages(self.cfg, depth,
                                         self.pipeline_stages)
        else:
            depth = snap_depth(self.cfg, depth)
        if not self._frozen or depth in self._steps:
            return depth
        deeper = sorted(k for k in self._steps
                        if isinstance(k, int) and k >= depth)
        if not deeper:
            raise KeyError(
                f"AOT step table has no entry at or deeper than depth "
                f"{depth}; available: {sorted(map(str, self._steps))} — "
                f"recompile the table or widen the exported depth set")
        if depth not in self._warned_depths:
            self._warned_depths.add(depth)
            import warnings
            warnings.warn(
                f"AOT step table missing depth {depth}; substituting "
                f"deeper entry {deeper[0]} (more backprop than scheduled)",
                stacklevel=3)
        return deeper[0]

    def depth_key_for_step(self, step: int) -> Any:
        if self.spb.mode in ("off", "spatial"):
            return None                 # spatial owns depth inside the step
        if self.spb.mode == "temporal-mb":
            return "mb"
        return self.resolve_depth(self.policy.depth_for_step(step))

    # -- training ----------------------------------------------------------

    _POLICY = object()          # sentinel: "ask the depth policy"

    def train_step(self, batch, step: Optional[int] = None, *,
                   depth: Any = _POLICY) -> Dict[str, jax.Array]:
        """Run one training step on the session state; the policy picks
        the depth unless ``depth`` overrides it (a table key: None, an
        int suffix depth, or 'mb').  Returns the metrics dict (state
        advances in place — the previous state's buffers are donated)
        as soon as the step is enqueued.  The call is the span
        ``engine.train_step`` and the enqueue inside it ``engine.enqueue``
        (:mod:`repro.obs`); the step's device time is in the trace."""
        if self.state is None:
            raise RuntimeError("call init_state()/attach_state() first")
        if step is None:
            step = self._auto_step
        with obs.span("engine.train_step", step=step) as span:
            key = (self.depth_key_for_step(step)
                   if depth is SPBEngine._POLICY else depth)
            fn = self.step_fn(key)
            with jax.sharding.set_mesh(self.mesh), obs.span("engine.enqueue"):
                self.state, metrics = fn(self.state, batch)
            self.last_depth = key
            self._auto_step = step + 1
            span.set_metadata(depth=str(key),
                              compiles=obs.counters()["compiles"])
        return metrics

    # -- AOT: lower / compile / export / import ----------------------------

    def batch_specs_like(self, batch) -> Any:
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), batch)

    def lower_step(self, batch_specs, *, depth: Any = None):
        """AOT-lower one step (any depth) against the session signatures;
        returns the jax Lowered (for HLO/cost analysis or .compile())."""
        with jax.sharding.set_mesh(self.mesh):
            return self._jit(depth).lower(self.state_shapes, batch_specs)

    def compile_table(self, batch_specs, *, depths=None) -> Dict[Any, Any]:
        """AOT lower+compile the step table.  Compiled entries replace the
        lazy jit wrappers, so subsequent train_step calls use them."""
        keys = list(self._raw) if depths is None else list(depths)
        for key in keys:
            if key in self._compiled:
                continue
            compiled = self.lower_step(batch_specs, depth=key).compile()
            self._compiled[key] = compiled
            self._steps[key] = compiled
        return dict(self._compiled)

    def memory_analysis(self, key: Any = None):
        """Memory analysis of a compiled entry (compile_table first)."""
        return self._compiled[key].memory_analysis()

    def aot_cache_path(self, batch_specs, cache_root=None) -> Path:
        root = Path(cache_root) if cache_root else aot.DEFAULT_CACHE
        extra = {}
        if self.parallelism != "spmd":
            extra.update(parallelism=self.parallelism,
                         pipeline_schedule=self.pipeline_schedule,
                         pipeline_data=self.pipeline_data,
                         tensor_parallel=self.tensor_parallel,
                         sequence_parallel=self.sequence_parallel,
                         zero2=self.zero2)
        if self.mesh.devices.size != jax.device_count():
            # a proper submesh: the executable is pinned to concrete
            # devices, so spatially co-located engines on *different*
            # submeshes must not share an artifact (same-submesh engines
            # still dedupe to one entry)
            extra["devices"] = [int(d.id) for d in self.mesh.devices.flat]
        return root / aot.cache_key(self.cfg, self.tcfg, self.spb, self.mesh,
                                    batch_specs, zero1=self.zero1,
                                    donate=self.donate,
                                    extra=extra or None)

    def export_aot(self, path, batch_specs=None) -> Path:
        """Serialize the compiled step table to ``path`` (compiling first
        if needed — requires ``batch_specs`` in that case)."""
        if not self._compiled:
            if batch_specs is None:
                raise ValueError("no compiled table; pass batch_specs")
            self.compile_table(batch_specs)
        return aot.export_table(
            self._compiled, Path(path),
            meta={"arch": self.cfg.name, "spb_mode": self.spb.mode,
                  "mesh_shape": list(self.mesh.devices.shape),
                  "mesh_axes": list(self.mesh.axis_names)})

    def load_aot(self, path) -> bool:
        """Import a serialized step table (no tracing/compiling).  Returns
        False when ``path`` has no table, or when what is there is damaged
        (corrupt manifest/bin, missing entry file) — a cache miss, so the
        caller re-traces; raises AOTCompatError on a genuine topology
        mismatch (the table is intact but for different hardware)."""
        if not aot.table_exists(path):
            return False
        try:
            table = aot.import_table(path, expect_mesh=self.mesh)
        except (aot.AOTCorruptError, FileNotFoundError):
            return False
        self._steps.update(table)
        self._frozen = True
        return True
