"""Flash attention's Pallas calls (the program's
``kernels/flash_attention*.py``).

Costs are of one call at the shape one device sees
(``counts.call_shape``): its rows, and its ``model`` shard's heads.
"""
from __future__ import annotations

from typing import Dict, Optional

from perfbench import counts

#: The jitted wrapper that launches the calls, as the trace names them.
WRAPPER = "_flash_attention_jit"
KINDS = ("flash_fwd", "flash_fwd_lse", "flash_delta", "flash_dq",
         "flash_dkv")


def kind(results, operands: str) -> Optional[str]:
    """A call's kind from the types of its ``results`` ((dtype, dims)
    pairs) and, for the single bf16 result, from its operands (q, k, v
    for the forward; q, k, v, do, lse, delta for dq)."""
    dtypes = [t for t, _ in results]
    if len(results) == 2:
        return "flash_fwd_lse" if dtypes[1] == "f32" else "flash_dkv"
    if len(results) == 1 and dtypes[0] == "f32":
        return "flash_delta"
    if len(results) == 1:
        n_in = operands.split("custom_call_target")[0].count("%")
        return "flash_dq" if n_in >= 6 else "flash_fwd"
    return None


def cost(hf, job, kind: str) -> Dict[str, float]:
    """Flops and bytes of one call of ``kind``, for one layer on one
    device."""
    _, s, d = counts.dims(hf, job)
    b, tp = counts.call_shape(job)["rows"], counts.call_shape(job)["model"]
    h = hf["num_attention_heads"] // tp
    kv = hf["num_key_value_heads"] // tp
    dh = d // hf["num_attention_heads"]
    q = b * h * s * dh * 2                # one bf16 (B, H, S, Dh) tensor
    kvb = b * kv * s * dh * 2
    rows = b * h * s * 4                  # one f32 (B, H, S, 1) vector
    mm = 2 * b * h * dh * counts.pairs(s)  # one matmul over the pairs
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
