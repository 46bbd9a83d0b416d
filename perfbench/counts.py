"""Operations and bytes the benchmark's training steps and kernels need,
from the configuration file's published keys and the job's shapes.

``step_flops`` is the numerator of ``mfu``: the forward of every layer and
of the output head, and the input and weight gradients of the layers the
step trains and of the head.  The lowest trained layer of a truncated step
needs no gradient of its own input, so its entry projections' input
gradients are left out.  A layer's forward is its architecture's
``layer_flops`` (``perfbench/archs/<model_type>.py``), its backward
twice its forward.  Recompute under remat, the embedding gather, norms
and other elementwise work are not counted.  The head counts
``vocab_size`` rows.

``kernel_cost`` gives one Pallas call's operations and bytes (HBM traffic
of its operands and results, each read or written once) for the
roofline share of that kernel, by the call's kind, from its family's
``cost`` (``perfbench/kernels/<family>.py``), at the shape one device
sees in one call (:func:`call_shape`): the rows of one microbatch on one
``data`` shard of the job's ``mesh``, and the heads of one ``model``
shard.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

from perfbench import registry


def dims(hf: Dict[str, Any], job: Dict[str, Any]):
    return job["batch"], job["seq_len"], hf["hidden_size"]


def pairs(s: int) -> int:
    """Causal query-key pairs of ``s`` positions."""
    return s * (s + 1) // 2


def step_flops(hf, job, depth: Optional[int]) -> float:
    """Flops one training step requires at suffix ``depth`` (None = all
    layers)."""
    b, s, d = dims(hf, job)
    n_layers = hf["num_hidden_layers"]
    depth = n_layers if depth is None else depth
    lf = registry.arch(hf["model_type"]).layer_flops(hf, job)
    fwd = lf["fwd_mm"] + lf["fwd_mixer"]
    head = 2 * b * s * d * hf["vocab_size"]
    total = n_layers * fwd + 3 * head + depth * 2 * fwd
    if depth < n_layers:
        total -= lf["entry_mm"]
    return float(total)


def call_shape(job) -> Dict[str, int]:
    """What one kernel call on one device holds of the job: its rows (the
    batch over the microbatches and the mesh's ``data`` axis) and its
    share of the heads (one over the ``model`` axis); a job without
    ``mesh`` runs on one device."""
    mesh = job.get("mesh", {})
    return {"rows": job["batch"] // job.get("microbatches", 1)
            // mesh.get("data", 1), "model": mesh.get("model", 1)}


def kernel_cost(hf, job, kind: str) -> Dict[str, float]:
    """Flops and bytes of one call of a kernel kind, for one layer on one
    device (:func:`call_shape`)."""
    family = registry.kernel_family(kind)
    if kind not in family.KINDS:
        raise ValueError(f"unknown kernel kind {kind!r}: not one of "
                         f"{family.__file__}'s {family.KINDS}")
    return family.cost(hf, job, kind)
