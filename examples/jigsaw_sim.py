"""Jigsaw cluster-scheduling example: run the paper's Fig-4 comparison on
a Philly-like trace and print the summary table.

  PYTHONPATH=src python examples/jigsaw_sim.py [--jobs 150] [--machines 45]
"""
import argparse
import statistics

from repro.analysis.roofline import DRYRUN_DEVICE_KIND
from repro.jigsaw.costmodel import profile_db
from repro.jigsaw.schedulers import ALL_SCHEDULERS
from repro.jigsaw.simulator import simulate
from repro.jigsaw.trace import generate_trace


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--jobs", type=int, default=150)
    ap.add_argument("--machines", type=int, default=45)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--arrival", type=float, default=2.0)
    ap.add_argument("--hlo-profiles", action="store_true",
                    help="use the dry-run-derived TPU arch profiles")
    args = ap.parse_args()

    db = profile_db(DRYRUN_DEVICE_KIND if args.hlo_profiles else None)
    kw = dict(num_jobs=args.jobs, seed=args.seed, db=db,
              mean_arrival_s=args.arrival, min_iters=100, max_iters=500)
    jobs_spb = generate_trace(spb=True, **kw)
    jobs_std = generate_trace(spb=False, **kw)

    print(f"{'scheduler':10s} {'makespan':>9s} {'util':>6s} {'medJCT':>8s} "
          f"{'p90 JCT':>8s} {'med mig':>8s}")
    base = None
    for name, cls in ALL_SCHEDULERS.items():
        jobs = jobs_spb if name == "jigsaw" else jobs_std
        r = simulate(jobs, cls(), num_machines=args.machines, horizon=2.0,
                     gamma=2.0)
        jcts = sorted(r.jct.values())
        migs = sorted(r.migration_fraction(j) for j in r.jct)
        print(f"{name:10s} {r.makespan:9.1f} {r.util:6.3f} "
              f"{statistics.median(jcts):8.1f} "
              f"{jcts[int(0.9*len(jcts))]:8.1f} "
              f"{statistics.median(migs):8.3f}")
        if name == "jigsaw":
            base = r.makespan
        elif base:
            print(f"{'':10s} -> jigsaw improves makespan by "
                  f"{100*(1-base/r.makespan):.1f}%")


if __name__ == "__main__":
    main()
