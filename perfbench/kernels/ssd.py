"""Mamba-2's SSD Pallas calls (the program's ``kernels/ssd*.py``): the
forward, the forward that keeps chunk states, the backward.

Costs are of one call at the rows one device sees
(``counts.call_shape``); the backward's flops are its own matmuls.
"""
from __future__ import annotations

from typing import Dict, Optional

from perfbench import counts

#: The jitted wrapper that launches the calls, as the trace names them.
WRAPPER = "_ssd_jit"
KINDS = ("ssd_fwd", "ssd_fwd_states", "ssd_bwd")


def kind(results, operands: str) -> Optional[str]:
    """A call's kind from the number of its ``results``."""
    return {2: "ssd_fwd", 3: "ssd_fwd_states", 4: "ssd_bwd"}.get(
        len(results))


def cost(hf, job, kind: str) -> Dict[str, float]:
    """Flops and bytes of one call of ``kind``, for one layer on one
    device."""
    _, s, d = counts.dims(hf, job)
    b = counts.call_shape(job)["rows"]
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
    # ssd_bwd: the backward kernel's own matmuls: C B^T and C S_in^T
    # again, x dS, B dS^T, G^T dy, dy x^T, M B, dy S_in, M^T C, (dy e)^T C
    flops = bh * chunks * (2 * qc * qc * (3 * n + 2 * p)
                           + 10 * qc * n * p)
    grads = bh * s * (p + 1 + 2 * n) * 4   # dx, ddA, dB, dC (f32)
    return {"flops": flops,
            "bytes": x_in + bc_in + da + states + y_out + state + grads}
