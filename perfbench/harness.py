"""One run of one benchmark cell: set-up, the measured window, the trace,
and the correctness comparison.

The program under test is the SPB trainer's normal path:
``launch.train.build_engine`` -> ``SPBEngine`` (``compile_table``,
``attach_state``, ``train_step``), fed by its own data layer
(``data.pipeline.Pipeline.get_batch``), on the job's mesh
(:func:`make_mesh`: a pipeline job's ``stage`` x ``data`` x ``model``,
else every chip on ``data``).  The benchmark makes the weights
(``weights.py``), times the window, reads the device and its trace, and
compares the first steps with the float32 reference (``reference.py``).

Set-up (counted in ``setup_s``): weights made on the device in one jitted
call from the seed; every depth the cell's policy runs compiled
(``compile_table``); then the first steps of the job run through the
window's own call and feed, one full depth cycle and at least three, and
the correctness readings of all of them are taken on the way, so that
every depth the window runs has a compared step and AdamW update.

The window is a closed loop like a trainer's: draw the next batch, enqueue
the step, block on the previous step's outputs, so one step stays in
flight.  A step's time is the interval between consecutive completions.
"""
from __future__ import annotations

import contextlib
import functools
import gc
import math
import sys
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

import numpy as np

from perfbench import counts, peaks as peaks_lib, reference, registry, weights

ROOT = registry.ROOT
CHECK_STEPS = 3


def log(msg: str) -> None:
    print(f"perfbench: {msg}", file=sys.stderr, flush=True)


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def tpu_devices(chips: int) -> list:
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"the benchmark needs a TPU; JAX found "
                     f"{devices[0].platform!r} devices")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX found "
                     f"{len(devices)}")
    return devices[:chips]


def import_program(root: Path) -> None:
    """Put the program's sources on the path."""
    src = str(Path(root) / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def enable_cache() -> None:
    """Turn on the program's persistent compilation cache, in the directory
    ``JAX_COMPILATION_CACHE_DIR`` names (the entry points set it to the
    checkout's ``.jax_cache/`` before JAX is imported)."""
    from repro.engine.stepcache import enable_compilation_cache
    enable_compilation_cache()


# -- the program's configuration ---------------------------------------------

def program_config(hf: Dict[str, Any]):
    """The trainer's ModelConfig for a configuration file, checked against
    the file's published keys: the shared ones and its architecture's
    ``program_keys``."""
    from repro.configs import get_config
    run = hf["trainer"]
    base = get_config(run["arch"])
    # an override given as a dict is a group of the trainer's config, made
    # as that group's own type
    overrides = {k: type(getattr(base, k))(**v) if isinstance(v, dict)
                 else v for k, v in run["overrides"].items()}
    cfg = base.scaled(**overrides)
    want = {"d_model": hf["hidden_size"],
            "num_layers": hf["num_hidden_layers"],
            "vocab_size": hf["vocab_size"],
            "norm_eps": hf["rms_norm_eps"],
            "tie_embeddings": hf["tie_word_embeddings"],
            "dtype": hf["torch_dtype"],
            **registry.arch(hf["model_type"]).program_keys(hf)}
    got = {k: functools.reduce(getattr, k.split("."), cfg) for k in want}
    # a trainer without the option keeps the residual stream in its
    # compute dtype, not in the float32 that some published configs state
    want["residual_in_fp32"] = hf.get("residual_in_fp32", False)
    got["residual_in_fp32"] = getattr(cfg, "residual_in_fp32", False)
    if got != want:
        raise ValueError(f"the trainer's config for {hf['trainer']} differs "
                         f"from the configuration file: "
                         f"{ {k: (got[k], want[k]) for k in want if got[k] != want[k]} }")
    return cfg


def train_config(job: Dict[str, Any]):
    from repro.config import TrainConfig
    o = job["optimizer"]
    return TrainConfig(learning_rate=o["learning_rate"], optimizer="adamw",
                       beta1=o["beta1"], beta2=o["beta2"], eps=o["eps"],
                       weight_decay=o["weight_decay"],
                       grad_clip=o["grad_clip"],
                       warmup_steps=o["warmup_steps"],
                       num_steps=o["num_steps"],
                       microbatches=job.get("microbatches", 1))


def depth_of(key, hf) -> int:
    return hf["num_hidden_layers"] if key is None else int(key)


def cycle_depths(job, hf) -> List[int]:
    """The SPB levels' depths, snapped as the job states them (every
    level's depth is a whole number of layers here)."""
    spb = job["spb"]
    n = hf["num_hidden_layers"]
    if spb["mode"] == "off":
        return [n]
    k = spb["k"]
    return [max(1, math.ceil((j + 1) * n / k)) for j in range(k)]


# -- meshes --------------------------------------------------------------------

def make_mesh(job: Dict[str, Any], devices: list):
    """The job's mesh over the cell's ``devices``: a pipeline job's
    ``mesh`` (``stage`` x ``data`` x ``model``, through the trainer's
    ``make_pipeline_mesh``), else every device on ``data``."""
    import jax
    if job["parallelism"] != "pipeline":
        return jax.sharding.Mesh(
            np.asarray(devices).reshape(len(devices), 1), ("data", "model"))
    from repro.launch.mesh import make_pipeline_mesh
    m = job["mesh"]
    if m["stage"] * m["data"] * m["model"] != len(devices):
        raise ValueError(f"the job's mesh {m} does not hold the cell's "
                         f"{len(devices)} chips")
    mesh = make_pipeline_mesh(m["stage"], data_parallel=m["data"],
                              model_parallel=m["model"])
    if {d.id for d in mesh.devices.flat} != {d.id for d in devices}:
        raise ValueError(f"the pipeline mesh's devices "
                         f"{[d.id for d in mesh.devices.flat]} are not the "
                         f"cell's {[d.id for d in devices]}")
    return mesh


def stage_of(mesh) -> Optional[Dict[int, int]]:
    """{device id: its pipeline stage}, or None for a mesh without one."""
    if "stage" not in mesh.axis_names:
        return None
    axis = mesh.axis_names.index("stage")
    return {int(d.id): int(i[axis]) for i, d in np.ndenumerate(mesh.devices)}


def spread(shapes, mesh):
    """Shardings that split each leaf of ``shapes`` over every device of
    ``mesh``, on its largest dim that the device count divides (the last
    of equal ones); a leaf with no such dim is whole on every device."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    n, axes = mesh.devices.size, tuple(mesh.axis_names)

    def one(leaf):
        dims = [i for i, s in enumerate(leaf.shape) if s % n == 0]
        if not dims:
            return NamedSharding(mesh, P())
        at = max(dims, key=lambda i: (leaf.shape[i], i))
        return NamedSharding(mesh, P(*[axes if i == at else None
                                       for i in range(len(leaf.shape))]))

    return jax.tree.map(one, shapes)


# -- the program under test ----------------------------------------------------

class Session:
    """The engine, its state, its data and its compiled steps, built once."""

    def __init__(self, cell: registry.Cell, seed: int, devices: list):
        import jax
        from repro.config import SPBConfig
        from repro.launch.train import build_engine
        self.cell, self.hf, self.job = cell, cell.hf, cell.job
        self.cfg = program_config(cell.hf)
        mesh = make_mesh(self.job, devices)
        spb = self.job["spb"]
        pipeline = ({"pipeline_schedule": self.job["schedule"],
                     "tensor_parallel": self.job["mesh"]["model"]}
                    if self.job["parallelism"] == "pipeline" else {})
        self.engine = build_engine(
            self.cfg, train_config(self.job),
            SPBConfig(mode=spb["mode"], k=spb.get("k", 4)), mesh,
            parallelism=self.job["parallelism"], **pipeline)
        n = len(cycle_depths(self.job, self.hf))
        self.keys = [self.engine.depth_key_for_step(s) for s in range(n)]
        if sorted(depth_of(k, self.hf) for k in self.keys) != \
                sorted(cycle_depths(self.job, self.hf)):
            raise ValueError(f"the trainer's depth cycle {self.keys} is not "
                             f"the job's {cycle_depths(self.job, self.hf)}")
        self.reset(seed)
        specs = self.engine.batch_specs_like(self.pipe.get_batch(0))
        self.compiled = self.engine.compile_table(
            specs, depths=list(dict.fromkeys(self.keys)))

    def reset(self, seed: int) -> None:
        """Fresh weights and data stream from ``seed``."""
        import jax
        from repro.data.pipeline import Pipeline
        self.engine.state = None
        gc.collect()
        state = jax.jit(functools.partial(weights.init_state, self.hf),
                        out_shardings=self.engine.state_shardings)(
                            weights.seed_key(seed))
        weights.check_layout(state, self.engine.state_shapes)
        self.engine.attach_state(state)
        self.seed = seed
        self.pipe = Pipeline(self.cfg, self.job["batch"], self.job["seq_len"],
                             seed=seed)

    def step(self, step: int, batch):
        """Enqueue one training step; returns its metrics and depth."""
        m = self.engine.train_step(batch, step)
        return m, depth_of(self.engine.last_depth, self.hf)

    def temp_bytes(self) -> Dict[int, int]:
        return {depth_of(k, self.hf): int(c.memory_analysis()
                                          .temp_size_in_bytes)
                for k, c in self.compiled.items()}

    def close(self) -> None:
        self.engine.state = None
        self.engine = None
        self.compiled = None
        gc.collect()


def check_batch(batch, vocab: int) -> None:
    """The data layer's contract: labels are the next tokens, ids lie in
    the vocabulary."""
    t = np.asarray(batch["tokens"])
    y = np.asarray(batch["labels"])
    if not (np.array_equal(t[:, 1:], y[:, :-1]) and t.min() >= 0
            and max(t.max(), y.max()) < vocab):
        raise ValueError("a batch breaks the data contract (labels are the "
                         "next tokens, ids in the vocabulary)")


# -- correctness readings -----------------------------------------------------

def _norm_fns(hf, seed):
    """Per-leaf norms of a tree, and of its change from the step-0 f32
    parameters of ``seed`` (made sharded as the tree is)."""
    import jax
    import jax.numpy as jnp
    key = weights.seed_key(seed)
    norms = jax.jit(lambda t: weights.leaf_norms(t, hf))

    def change(m):
        like = jax.tree.map(lambda a: a.sharding, m)
        return jax.jit(lambda m, key: weights.leaf_norms(
            jax.tree.map(jnp.subtract, m, jax.lax.with_sharding_constraint(
                weights.master_params(hf, key), like)), hf))(m, key)

    return norms, change


def n_first_steps(sess: Session) -> int:
    """The job's first steps that set-up runs and the reference follows:
    one whole depth cycle, and at least :data:`CHECK_STEPS`."""
    return max(len(sess.keys), CHECK_STEPS)


def first_steps(sess: Session) -> Dict[str, Any]:
    """Run the job's first :func:`n_first_steps` steps through the
    window's call and feed; read the program's loss at each, its first
    clipped and rescaled gradient (from AdamW's first moment after one
    step) and its parameters' change over all of them (the f32 master
    copy that the next step starts from)."""
    import jax
    hf, job = sess.hf, sess.job
    b1 = job["optimizer"]["beta1"]
    norms, change = _norm_fns(hf, sess.seed)
    out = {"loss": [], "depths": [], "batches": []}
    for s in range(n_first_steps(sess)):
        batch = sess.pipe.get_batch(s)
        check_batch(batch, hf["vocab_size"])
        out["batches"].append({k: np.asarray(v) for k, v in batch.items()})
        m, d = sess.step(s, batch)
        out["loss"].append(float(m["loss"]))
        out["depths"].append(d)
        if s == 0:
            out["grad"] = np.asarray(norms(sess.engine.state["opt"]["mu"])) \
                / (1.0 - b1)
    st = sess.engine.state              # float32 params have no master
    out["change"] = np.asarray(change(st["opt"].get("master", st["params"])))
    jax.block_until_ready(sess.engine.state)
    return out


def reference_readings(hf, job, seed: int, batches, depths,
                       variant=reference.Variant(), devices=None
                       ) -> Dict[str, Any]:
    """The same first steps, by the reference (or a variant of it put in
    the program's place), on the cell's ``devices`` (default: the first):
    the f32 parameters, their gradient and AdamW's moments each split
    over all of them (:func:`spread`), the batch whole on each."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.asarray(devices or jax.devices()[:1]), ("all",))
    _, change = _norm_fns(hf, seed)
    job = dict(job, depths=cycle_depths(job, hf))
    key = weights.seed_key(seed)
    start = functools.partial(weights.master_params, hf)
    pshard = spread(jax.eval_shape(start, key), mesh)
    mshard = {"mu": pshard, "nu": pshard}
    whole = NamedSharding(mesh, P())
    params = jax.jit(start, out_shardings=pshard)(key)
    zeros = jax.jit(lambda p: {"mu": jax.tree.map(jnp.zeros_like, p),
                               "nu": jax.tree.map(jnp.zeros_like, p)},
                    out_shardings=mshard)

    def grad(p, tokens, labels, *, depth):
        loss, g = reference.gradient(p, tokens, labels, hf=hf, job=job,
                                     depth=depth, variant=variant)
        return loss, g, weights.leaf_norms(g, hf)

    update = jax.jit(functools.partial(reference.adamw, job=job),
                     out_shardings=(pshard, mshard), donate_argnums=(0, 1))
    out = {"loss": [], "depths": list(depths)}
    # on one device the moments wait on the host between steps, so that
    # the gradient's activations fit beside the rest; split over several
    # they stay
    park = mesh.devices.size == 1
    moments = None
    with jax.default_matmul_precision("highest"):
        fns = {d: jax.jit(functools.partial(grad, depth=d),
                          out_shardings=(whole, pshard, whole))
               for d in set(depths)}
        for i, (batch, d) in enumerate(zip(batches, depths)):
            loss, g, gn = fns[d](params,
                                 jax.device_put(batch["tokens"], whole),
                                 jax.device_put(batch["labels"], whole))
            out["loss"].append(float(loss))
            if i == 0:
                out["grad"] = np.asarray(gn)
            m = (zeros(params) if moments is None
                 else jax.device_put(moments, mshard))
            params, m = update(params, m, g, i)
            del g
            moments = jax.device_get(m) if park else m
            del m
        out["change"] = np.asarray(change(params))
    del params, moments
    gc.collect()
    return out


def compare(prog: Dict[str, Any], ref: Dict[str, Any], names: List[str]
            ) -> Dict[str, Dict[str, Any]]:
    """The numbers compared: the largest relative gap of the step losses,
    and, by the worst leaf, the gap between the program's and the
    reference's norms of the first gradient and of the change over the
    first steps, each against the reference's norm of that
    leaf or of the median leaf, whichever is larger.  Leaves whose
    reference gradient is under a thousandth of the median leaf's are left
    out of the change."""
    lp, lr = np.asarray(prog["loss"]), np.asarray(ref["loss"])
    loss_gaps = np.abs(lp - lr) / np.abs(lr)
    gp, gr = np.asarray(prog["grad"]), np.asarray(ref["grad"])
    gmed = float(np.median(gr))
    grad_gaps = np.abs(gp - gr) / np.maximum(gr, gmed)
    keep = gr >= 1e-3 * gmed
    cp, cr = np.asarray(prog["change"])[keep], np.asarray(ref["change"])[keep]
    kept = [n for n, k in zip(names, keep) if k]
    cmed = float(np.median(cr))
    change_gaps = np.abs(cp - cr) / np.maximum(cr, cmed)

    def worst(gaps, labels):
        gaps = np.where(np.isfinite(gaps), gaps, np.inf)
        i = int(np.argmax(gaps))
        return {"value": float(gaps[i]), "at": labels[i]}

    if prog["depths"] != ref["depths"]:
        raise ValueError(f"the program ran depths {prog['depths']}, the "
                         f"reference {ref['depths']}")
    return {"loss_gap": worst(loss_gaps, [f"step {i}" for i in
                                          range(len(lp))]),
            "grad_gap": worst(grad_gaps, names),
            "update_gap": worst(change_gaps, kept),
            "left_out": [n for n, k in zip(names, keep) if not k]}


NUMBERS = ("loss_gap", "grad_gap", "update_gap")


def judge(numbers: Dict[str, Dict[str, Any]], limits: Dict[str, Any]
          ) -> Dict[str, Dict[str, Any]]:
    """Each compared number beside its limit from the limits file's
    ``limits``, which has to give one for every number and no other."""
    if sorted(limits) != sorted(NUMBERS):
        raise ValueError(f"the limits file gives limits for {sorted(limits)}"
                         f"; it has to give one for each of {NUMBERS}")
    return {k: {"value": numbers[k]["value"], "limit": limits[k],
                "at": numbers[k]["at"]} for k in NUMBERS}


def correct(checks: Dict[str, Dict[str, Any]]) -> bool:
    return all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in checks.values())


# -- the window ----------------------------------------------------------------

def _mem(device, key: str) -> int:
    """A device allocator statistic (0 where the backend reports none)."""
    return int((device.memory_stats() or {}).get(key, 0))


def window(sess: Session, first_step: int, seconds: float, devices,
           tracing: bool) -> Dict[str, Any]:
    import jax
    ann = (jax.profiler.TraceAnnotation if tracing
           else (lambda name: contextlib.nullcontext()))
    steps: List[Dict[str, Any]] = []
    losses = []
    in_use = 0
    step = first_step
    prev = None
    with ann("bench.window"):
        t0 = time.perf_counter()
        done = [t0]
        while True:
            a = time.perf_counter()
            with ann("bench.get_batch"):
                batch = sess.pipe.get_batch(step)
            b = time.perf_counter()
            with ann("bench.train_step"):
                m, depth = sess.step(step, batch)
            c = time.perf_counter()
            in_use = max(in_use, max(_mem(d, "bytes_in_use")
                                     for d in devices))
            steps.append({"depth": depth, "data_s": b - a,
                          "dispatch_s": c - b})
            losses.append(m["loss"])
            if prev is not None:
                with ann("bench.wait"):
                    jax.block_until_ready(prev)
                done.append(time.perf_counter())
            prev = m
            step += 1
            if time.perf_counter() - t0 >= seconds:
                break
        with ann("bench.wait"):
            jax.block_until_ready(prev)
        done.append(time.perf_counter())
    for s, a, b in zip(steps, done, done[1:]):
        s["interval_s"] = b - a
    losses = np.asarray(jax.device_get(losses), np.float64)
    return {"t0": t0, "steps": steps, "window_s": done[-1] - t0,
            "in_use_bytes": in_use,
            "failed": int(np.sum(~np.isfinite(losses)))}


def slow_steps(steps: List[Dict[str, Any]], factor: float = 1.5
               ) -> List[Dict[str, Any]]:
    """The window's steps that took over ``factor`` times the median step
    of their depth, with the host's share of each (a record of stalls)."""
    med = {d: float(np.median([s["interval_s"] for s in steps
                               if s["depth"] == d]))
           for d in {s["depth"] for s in steps}}
    return [{"index": i, "depth": s["depth"],
             "interval_ms": s["interval_s"] * 1e3,
             "median_ms": med[s["depth"]] * 1e3,
             "data_ms": s["data_s"] * 1e3, "dispatch_ms": s["dispatch_s"] * 1e3}
            for i, s in enumerate(steps)
            if s["interval_s"] > factor * med[s["depth"]]]


# -- one run --------------------------------------------------------------------

def run(cell: registry.Cell, seed: int, seconds: float, trace: bool,
        devices: list, t_start: float, root: Path = ROOT,
        trace_dir: Optional[Path] = None) -> Dict[str, Any]:
    """One run of ``cell``; returns the result line's object."""
    import jax
    hf, job = cell.hf, cell.job
    if cell.limits is None:
        raise FileNotFoundError(f"no limits file for {cell.name}")
    kind = devices[0].device_kind
    pk = peaks_lib.peaks(kind) if devices[0].platform == "tpu" else None
    log(f"start {time.perf_counter() - t_start:.1f} s")
    sess = Session(cell, seed, devices)
    stages = stage_of(sess.engine.mesh)
    log(f"weights and compiles {time.perf_counter() - t_start:.1f} s")
    prog = first_steps(sess)
    first = n_first_steps(sess)
    temp = sess.temp_bytes()
    log(f"first steps {time.perf_counter() - t_start:.1f} s")

    tracer = contextlib.nullcontext()
    if trace:
        trace_dir = Path(trace_dir or (root / "perfbench_out" / "trace"))
        if trace_dir.exists():
            import shutil
            shutil.rmtree(trace_dir)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        opts.enable_hlo_proto = False
        tracer = jax.profiler.trace(str(trace_dir), profiler_options=opts)
    setup_s = time.perf_counter() - t_start
    with tracer:
        win = window(sess, first, seconds, devices, trace)
    peak = max(_mem(d, "peak_bytes_in_use") for d in devices)
    stats = devices[0].memory_stats() or {}
    sess.close()
    del sess
    gc.collect()

    t_ref = time.perf_counter()
    ref = reference_readings(hf, job, seed, prog["batches"], prog["depths"],
                             devices=devices)
    log(f"reference {time.perf_counter() - t_ref:.1f} s")
    numbers = compare(prog, ref, weights.leaf_names(hf))
    checks = judge(numbers, cell.limits["limits"])

    n = len(win["steps"])
    tokens = n * job["batch"] * job["seq_len"]
    intervals = [s["interval_s"] for s in win["steps"]]
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    result: Dict[str, Any] = {"correct": correct(checks) and win["failed"] == 0,
                              "attempted": n, "failed": win["failed"]}
    if not trace:
        e2e = {"tokens_per_s": (tokens / win["window_s"], "tokens/s"),
               "step_ms_p90": (float(np.percentile(intervals, 90)) * 1e3, "ms"),
               "peak_hbm_gb": (peak / 1e9, "GB"),
               "setup_s": (setup_s, "s")}
        result["metrics"] = {m["name"]: {"value": e2e[m["name"]][0],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
    else:
        from perfbench import readers, tracefile
        tr = tracefile.load(tracefile.find(str(trace_dir)))
        lo = [a for a, b, nm in tr["host"] if nm == "bench.window"]
        hi = [b for a, b, nm in tr["host"] if nm == "bench.window"]
        summary = tracefile.summarize(tr, lo[0], hi[0],
                                      [s["depth"] for s in win["steps"]])
        record = {"hf": hf, "job": job, "chips": len(devices), "peaks": pk,
                  "stage_of": stages,
                  "steps": win["steps"], "window_s": win["window_s"],
                  "temp_bytes": temp, "trace": summary,
                  "flops": {d: counts.step_flops(hf, job, d)
                            for d in set(s["depth"] for s in win["steps"])}}
        metrics = {}
        for m in cell.per_layer:
            v = registry.reader(m["name"], root)(record)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        result["metrics"] = metrics
        device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
        if stages:
            result["breakdown"]["stages"] = readers.stage_split(record)
    result["device"] = device
    result["setup"] = {"setup_s": setup_s, "in_window_bytes_in_use_max":
                       win["in_use_bytes"], "step_temp_bytes": temp,
                       "memory_stats_after_window": stats,
                       "left_out_of_change": numbers["left_out"],
                       "first_steps_depths": prog["depths"],
                       "slow_steps": slow_steps(win["steps"])}
    result["checks"] = checks
    return result
