"""data layer: median host time of the token draw (``MarkovLM.sample``,
the program's ``data.sample`` span) in one ``Pipeline.get_batch`` of the
window, the steps told apart by ``data.get_batch``'s ``step`` stat, in ms."""
from perfbench import spans


def read(rec):
    w = spans.window(rec)
    if w is None:
        return None
    return spans.median_ms(
        kids for _, kids in spans.per_step(w, "data.get_batch",
                                           "data.sample").values())
