"""data layer: device idle time in the traced window whose innermost
overlapping program span is one of the data layer's (``data.*``), summed,
in ms: what the device waits for the next batch."""
from perfbench import spans


def read(rec):
    w = spans.window(rec)
    if w is None or w["busy"] is None or not w["spans"]["data.get_batch"]:
        return None
    return spans.idle_under(w, "data.") / 1e6
