"""Paper Fig 4: Jigsaw vs Tiresias/Gandiva/FIFO on a Philly-like trace.

(a) makespan on a 45-machine cluster, (b) JCT distribution, (c) migration
fraction CDF.  Jigsaw runs SPB jobs (iteration-level scheduling exploits
the per-worker asymmetry); baselines run standard symmetric jobs (their
APIs cannot express SPB — the paper's comparison).  An ablation runs
Jigsaw WITHOUT SPB to isolate scheduler vs technique.
"""
from __future__ import annotations

import json
import platform
import statistics
from pathlib import Path
from typing import Dict, List

from repro.analysis.roofline import DRYRUN_DEVICE_KIND
from repro.jigsaw.costmodel import profile_db, v100_profiles
from repro.jigsaw.schedulers import ALL_SCHEDULERS, JigsawScheduler
from repro.jigsaw.simulator import simulate
from repro.jigsaw.trace import generate_trace

OUT = Path(__file__).resolve().parents[1] / "BENCH_fig4_scheduler.json"


def bench(num_jobs: int = 150, machines: int = 45, seed: int = 1,
          mean_arrival: float = 2.0, use_hlo_profiles: bool = False
          ) -> Dict[str, dict]:
    db = (profile_db(DRYRUN_DEVICE_KIND) if use_hlo_profiles
          else v100_profiles())
    kw = dict(num_jobs=num_jobs, seed=seed, db=db,
              mean_arrival_s=mean_arrival, min_iters=100, max_iters=500)
    jobs_spb = generate_trace(spb=True, **kw)
    jobs_std = generate_trace(spb=False, **kw)
    results = {}
    for name, cls in ALL_SCHEDULERS.items():
        jobs = jobs_spb if name == "jigsaw" else jobs_std
        r = simulate(jobs, cls(), num_machines=machines, horizon=2.0,
                     gamma=2.0)
        jcts = sorted(r.jct.values())
        migs = sorted(r.migration_fraction(j) for j in r.jct)
        results[name] = {
            "makespan": r.makespan,
            "util": r.util,
            "jct_p50": statistics.median(jcts),
            "jct_mean": statistics.mean(jcts),
            "jct_p90": jcts[int(0.9 * len(jcts))],
            "mig_p50": statistics.median(migs),
            "mig_p90": migs[int(0.9 * len(migs))],
        }
    # ablation: jigsaw scheduling w/o the SPB technique
    r = simulate(jobs_std, JigsawScheduler(), num_machines=machines,
                 horizon=2.0, gamma=2.0)
    results["jigsaw_nospb"] = {
        "makespan": r.makespan, "util": r.util,
        "jct_p50": statistics.median(sorted(r.jct.values())),
        "jct_mean": statistics.mean(r.jct.values()),
        "jct_p90": sorted(r.jct.values())[int(0.9 * len(r.jct))],
        "mig_p50": 0.0, "mig_p90": 0.0,
    }
    return results


def write_json(res: Dict[str, dict], *, num_jobs: int, machines: int,
               seed: int, mean_arrival: float, quick: bool,
               path: Path = OUT) -> Path:
    """Machine-readable perf trajectory alongside the printed table, like
    BENCH_spb_step.json: makespan + utilization (+ JCT/migration
    percentiles) per scheduler, and Jigsaw's makespan improvement over
    each baseline."""
    base = res["jigsaw"]["makespan"]
    rec = {
        "num_jobs": num_jobs, "machines": machines, "seed": seed,
        "mean_arrival_s": mean_arrival, "quick": quick,
        "platform": platform.platform(),
        "schedulers": res,
        "jigsaw_improvement_pct": {
            b: round(100 * (1 - base / res[b]["makespan"]), 2)
            for b in ("tiresias", "gandiva", "fifo")},
    }
    path.write_text(json.dumps(rec, indent=2) + "\n")
    return path


def run(quick: bool = True):
    num_jobs = 80 if quick else 250
    mean_arrival = 2.0 if quick else 1.5
    machines, seed = 45, 1
    res = bench(num_jobs=num_jobs, machines=machines, seed=seed,
                mean_arrival=mean_arrival)
    write_json(res, num_jobs=num_jobs, machines=machines, seed=seed,
               mean_arrival=mean_arrival, quick=quick)
    out = []
    base = res["jigsaw"]["makespan"]
    for name, r in res.items():
        out.append((f"fig4/{name}", r["makespan"] * 1e6,
                    f"makespan={r['makespan']:.0f}s util={r['util']:.3f} "
                    f"jct_p50={r['jct_p50']:.0f} jct_p90={r['jct_p90']:.0f} "
                    f"mig_p50={r['mig_p50']:.3f}"))
    for b in ("tiresias", "gandiva", "fifo"):
        gain = 100 * (1 - base / res[b]["makespan"])
        out.append((f"fig4/jigsaw_vs_{b}", 0.0,
                    f"makespan_improvement={gain:.1f}%"))
    return out


if __name__ == "__main__":
    for name, us, derived in run(quick=False):
        print(f"{name},{us:.1f},{derived}")
