"""step: median device time of one step program at the shallowest depth
the cell runs, from the trace, in ms; nothing where every step is full."""
from perfbench import readers


def read(rec):
    shallow = min(s["depth"] for s in rec["steps"])
    if shallow == rec["hf"]["num_hidden_layers"]:
        return None
    return readers.step_device_ms(rec, shallow)
