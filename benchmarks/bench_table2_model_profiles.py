"""Paper Table 2: per-model forward/backward profiles + gradient sizes.

The paper profiled 9 CNNs on a V100; our equivalents are the 10 assigned
architectures with profiles derived from the compiled dry-run: per-device
HLO FLOPs/bytes -> roofline step-time estimates, plus analytic parameter /
gradient sizes.  Reduced-config wall-times on this host are measured too.
"""
from __future__ import annotations

import time
from typing import List

import jax

from repro.analysis.roofline import (DRYRUN_DEVICE_KIND, count_params,
                                     load_record, peaks)
from repro.configs import get_config, list_archs, make_batch, reduced_config
from repro.models import lm


def compiled_profiles() -> List[dict]:
    rows = []
    for arch in list_archs():
        cfg = get_config(arch)
        n = count_params(cfg)
        rec = load_record(arch, "train_4k")
        row = {
            "arch": arch,
            "params_b": round(n["total"] / 1e9, 3),
            "active_b": round(n["active"] / 1e9, 3),
            "grad_gb": round(n["nonembed"] * 2 / 2 ** 30, 2),   # bf16
        }
        if rec:
            pk = peaks(DRYRUN_DEVICE_KIND)
            step = max(rec["flops_per_device"] / pk.flops,
                       rec["bytes_per_device"] / pk.hbm_bw,
                       rec["collective_bytes_per_device"] / pk.link_bw)
            # fwd ~ 1/3 of a full train step (fwd:bwd ~ 1:2)
            row.update({
                "est_step_s": round(step, 3),
                "est_fwd_s": round(step / 3, 3),
                "est_bwd_s": round(2 * step / 3, 3),
            })
        rows.append(row)
    return rows


def measured_reduced(reps: int = 2) -> List[dict]:
    rows = []
    for arch in list_archs():
        cfg = reduced_config(arch)
        params = lm.init_lm(jax.random.key(0), cfg)
        b = make_batch(cfg, 2, 64)
        fwd = jax.jit(lambda p, bb, c=cfg: lm.loss_fn(p, bb, c)[0])
        bwd = jax.jit(lambda p, bb, c=cfg: jax.grad(
            lambda pp: lm.loss_fn(pp, bb, c)[0])(p))
        jax.block_until_ready(fwd(params, b))
        jax.block_until_ready(bwd(params, b))
        t0 = time.perf_counter()
        for _ in range(reps):
            jax.block_until_ready(fwd(params, b))
        f_ms = (time.perf_counter() - t0) / reps * 1e3
        t0 = time.perf_counter()
        for _ in range(reps):
            jax.block_until_ready(bwd(params, b))
        t_ms = (time.perf_counter() - t0) / reps * 1e3
        rows.append({"arch": arch, "fwd_ms": round(f_ms, 1),
                     "bwd_ms": round(max(t_ms - f_ms, 0), 1)})
    return rows


def run(quick: bool = True):
    out = []
    for r in compiled_profiles():
        derived = (f"params={r['params_b']}B active={r['active_b']}B "
                   f"grad={r['grad_gb']}GiB")
        if "est_step_s" in r:
            derived += (f" est_fwd={r['est_fwd_s']}s "
                        f"est_bwd={r['est_bwd_s']}s")
        out.append((f"table2/compiled/{r['arch']}", 0.0, derived))
    for r in measured_reduced(reps=1 if quick else 5):
        out.append((f"table2/measured/{r['arch']}",
                    (r["fwd_ms"] + r["bwd_ms"]) * 1e3,
                    f"fwd={r['fwd_ms']}ms bwd={r['bwd_ms']}ms"))
    return out


if __name__ == "__main__":
    for name, us, derived in run():
        print(f"{name},{us:.1f},{derived}")
