"""engine: XLA compiles (and persistent-cache loads) in the traced window:
the program's ``compiles`` counter on the window's last
``engine.train_step`` less its first, steps told apart by their ``step``
stat.  Each span records the counter as the call returns."""
from perfbench import spans


def read(rec):
    w = spans.window(rec)
    if w is None:
        return None
    by_step = {s[3]["step"]: s[3]["compiles"]
               for s in w["spans"]["engine.train_step"]}
    return by_step[max(by_step)] - by_step[min(by_step)]
