"""engine: median host time of one ``SPBEngine.train_step`` call until it
returns (the step enqueued, not finished; the ``bench.train_step`` span),
in ms."""
import statistics


def read(rec):
    xs = [s["dispatch_s"] for s in rec["steps"]]
    return statistics.median(xs) * 1e3 if xs else None
