"""engine: median host time of one ``SPBEngine.train_step`` less the step
program's call inside it (``engine.train_step`` less its
``engine.enqueue``): the depth policy, the depth's resolution and the table
lookup, in ms."""
from perfbench import spans


def read(rec):
    w = spans.window(rec)
    if w is None:
        return None
    return spans.median_ms(
        whole - enqueue for whole, enqueue in spans.per_step(
            w, "engine.train_step", "engine.enqueue").values())
