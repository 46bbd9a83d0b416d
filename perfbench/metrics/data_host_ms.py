"""data layer: median host time of one ``Pipeline.get_batch`` call in the
window (the benchmark's ``bench.get_batch`` span), in ms."""
import statistics


def read(rec):
    xs = [s["data_s"] for s in rec["steps"]]
    return statistics.median(xs) * 1e3 if xs else None
