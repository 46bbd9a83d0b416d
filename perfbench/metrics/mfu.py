"""model: the flops every window step required at its depth
(``counts.step_flops``), summed, over the window's seconds, the chips and
the chip's peak, in %."""


def read(rec):
    if not rec.get("peaks") or not rec["steps"]:
        return None
    work = sum(rec["flops"][s["depth"]] for s in rec["steps"])
    return 100.0 * work / (rec["window_s"] * rec["chips"]
                           * rec["peaks"]["flops"])
