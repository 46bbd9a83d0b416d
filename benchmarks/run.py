"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  ``--full`` runs the longer
protocols; the default quick mode keeps CPU runtime manageable.  The
roofline table (EXPERIMENTS.md §Roofline) is appended from the cached
dry-run records when they exist.
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default="",
                    help="comma-separated substring filter on bench names")
    args, _ = ap.parse_known_args()
    quick = not args.full

    from benchmarks import (bench_fault_recovery, bench_fig4_scheduler,
                            bench_table1_spb_resources,
                            bench_table2_model_profiles, bench_table3_quality)
    modules = [
        ("table1", bench_table1_spb_resources),
        ("table2", bench_table2_model_profiles),
        ("table3+fig3", bench_table3_quality),
        ("fig4", bench_fig4_scheduler),
        ("fault_recovery", bench_fault_recovery),
    ]
    only = [s for s in args.only.split(",") if s]
    failures = 0
    for name, mod in modules:
        if only and not any(s in name for s in only):
            continue
        t0 = time.time()
        try:
            rows = mod.run(quick=quick)
            for rname, us, derived in rows:
                print(f"{rname},{us:.1f},{derived}")
            print(f"# {name} done in {time.time()-t0:.1f}s", file=sys.stderr)
        except Exception:       # noqa: BLE001
            failures += 1
            print(f"# {name} FAILED:\n{traceback.format_exc()}",
                  file=sys.stderr)

    # roofline summary (from dry-run cache)
    try:
        from repro.analysis.roofline import DRYRUN_DEVICE_KIND, full_table
        for r in full_table(DRYRUN_DEVICE_KIND):
            print(f"roofline/{r.arch}/{r.shape},0.0,"
                  f"compute={r.compute_s:.4f}s memory={r.memory_s:.4f}s "
                  f"collective={r.collective_s:.4f}s bound={r.dominant} "
                  f"mfu={r.mfu:.4f} useful={r.useful_ratio:.2f}")
    except Exception:           # noqa: BLE001
        print(f"# roofline summary unavailable:\n{traceback.format_exc()}",
              file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
