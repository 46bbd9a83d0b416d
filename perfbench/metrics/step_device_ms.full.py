"""step: median device time of one full-depth step program, from the
trace, in ms."""
from perfbench import readers


def read(rec):
    return readers.step_device_ms(rec, rec["hf"]["num_hidden_layers"])
