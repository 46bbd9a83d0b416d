"""engine: host time in Python garbage collections in the traced window
(the program's ``host.gc`` spans, cut to the window), summed, in ms."""
from perfbench import spans


def read(rec):
    w = spans.window(rec)
    if w is None:
        return None
    return sum(b - a for _, a, b, _ in w["spans"]["host.gc"]) / 1e6
