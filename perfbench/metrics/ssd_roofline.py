"""kernels: the SSD Pallas calls' (forward, forward with chunk states,
backward) share of their roofline, in %."""
from perfbench import readers


def read(rec):
    return readers.roofline_share(rec, "ssd_")
