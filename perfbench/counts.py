"""Operations and bytes the benchmark's training steps and kernels need,
from the configuration file's published keys and the job's shapes.

``step_flops`` is the numerator of ``mfu``: the forward of every layer and
of the output head, and the input and weight gradients of the layers the
step trains and of the head.  The lowest trained layer of a truncated step
needs no gradient of its own input, so its entry projections' input
gradients are left out.  Causal attention counts S(S+1)/2 query-key pairs
per head; the SSD counts its chunked algorithm's matmuls (intra-chunk
(Q x Q) terms, chunk states, inter-chunk output), its backward twice its
forward.  Recompute under remat, the embedding gather, norms and other
elementwise work are not counted.  The head counts ``vocab_size`` rows.

``kernel_cost`` gives one Pallas call's operations and bytes (HBM traffic
of its operands and results, each read or written once) for the
roofline share of that kernel, by the call's kind, at the shape one
device sees in one call: the rows of one microbatch on one ``data`` shard
of the job's ``mesh``, and the heads of one ``model`` shard.
"""
from __future__ import annotations

from typing import Any, Dict, Optional


def _dims(hf: Dict[str, Any], job: Dict[str, Any]):
    return job["batch"], job["seq_len"], hf["hidden_size"]


def _pairs(s: int) -> int:
    return s * (s + 1) // 2


def layer_flops(hf, job) -> Dict[str, float]:
    """One layer's forward flops and those of its entry projections."""
    b, s, d = _dims(hf, job)
    t = b * s
    if hf["model_type"] == "llama":
        h, kv = hf["num_attention_heads"], hf["num_key_value_heads"]
        dh = d // h
        entry = d * (h + 2 * kv) * dh
        mats = entry + h * dh * d + 3 * d * hf["intermediate_size"]
        mixer = 4 * b * h * dh * _pairs(s)              # QK^T and PV
        return {"fwd_mm": 2 * t * mats, "fwd_mixer": mixer,
                "entry_mm": 2 * t * entry}
    if hf["model_type"] == "mamba2":
        c = hf["ssm_cfg"]
        d_in = c["expand"] * d
        nh = d_in // c["headdim"]
        gn = c["ngroups"] * c["d_state"]
        p, n, q = c["headdim"], c["d_state"], c["chunk_size"]
        entry = d * (2 * d_in + 2 * gn + nh)
        mats = entry + d_in * d
        conv = 2 * t * c["d_conv"] * (d_in + 2 * gn)
        ssd = t * nh * (2 * q * (n + p) + 4 * n * p)
        return {"fwd_mm": 2 * t * mats + conv, "fwd_mixer": ssd,
                "entry_mm": 2 * t * entry}
    raise ValueError(hf["model_type"])


def step_flops(hf, job, depth: Optional[int]) -> float:
    """Flops one training step requires at suffix ``depth`` (None = all
    layers)."""
    b, s, d = _dims(hf, job)
    n_layers = hf["num_hidden_layers"]
    depth = n_layers if depth is None else depth
    lf = layer_flops(hf, job)
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
    _, s, d = _dims(hf, job)
    b, tp = call_shape(job)["rows"], call_shape(job)["model"]
    if kind.startswith("flash_"):
        h = hf["num_attention_heads"] // tp
        kv = hf["num_key_value_heads"] // tp
        dh = d // hf["num_attention_heads"]
        q = b * h * s * dh * 2                # one bf16 (B, H, S, Dh) tensor
        kvb = b * kv * s * dh * 2
        rows = b * h * s * 4                  # one f32 (B, H, S, 1) vector
        mm = 2 * b * h * dh * _pairs(s)       # one matmul over the pairs
        return {
            # q, k, v in; o out (the forward of a layer that trains not)
            "flash_fwd": {"flops": 2 * mm, "bytes": 2 * q + 2 * kvb},
            # q, k, v in; o and lse out (the residuals of the backward)
            "flash_fwd_lse": {"flops": 2 * mm,
                              "bytes": 2 * q + 2 * kvb + rows},
            # rowsum(o * do): o, do in; delta out
            "flash_delta": {"flops": 2 * b * h * s * dh,
                            "bytes": 2 * q + rows},
            # S = QK^T, dP = dO V^T, dQ = dS K; q, k, v, do, lse, delta in
            "flash_dq": {"flops": 3 * mm, "bytes": 3 * q + 2 * kvb + 2 * rows},
            # S, dP, dV = P^T dO, dK = dS^T Q; dk, dv out
            "flash_dkv": {"flops": 4 * mm,
                          "bytes": 2 * q + 4 * kvb + 2 * rows},
        }[kind]
    if kind.startswith("ssd_"):
        c = hf["ssm_cfg"]
        nh = c["expand"] * d // c["headdim"]
        p, n, qc = c["headdim"], c["d_state"], c["chunk_size"]
        bh, chunks = b * nh, s // qc
        x_in = bh * s * p * 2                 # bf16 x*dt
        bc_in = 2 * bh * s * n * 2            # bf16 B and C
        da = bh * s * 4                       # f32 dt*A
        y_out = bh * s * p * 4                # f32 y
        state = bh * p * n * 4
        states = bh * chunks * p * n * 4
        fwd_flops = bh * chunks * (2 * qc * qc * (n + p) + 4 * qc * n * p)
        if kind == "ssd_fwd":
            return {"flops": fwd_flops,
                    "bytes": x_in + bc_in + da + y_out + state}
        if kind == "ssd_fwd_states":
            return {"flops": fwd_flops,
                    "bytes": x_in + bc_in + da + y_out + state + states}
        if kind == "ssd_bwd":
            # the backward kernel's own matmuls: C B^T and C S_in^T again,
            # x dS, B dS^T, G^T dy, dy x^T, M B, dy S_in, M^T C, (dy e)^T C
            flops = bh * chunks * (2 * qc * qc * (3 * n + 2 * p)
                                   + 10 * qc * n * p)
            grads = bh * s * (p + 1 + 2 * n) * 4   # dx, ddA, dB, dC (f32)
            return {"flops": flops,
                    "bytes": x_in + bc_in + da + states + y_out + state
                    + grads}
    raise ValueError(f"unknown kernel kind {kind!r}")
