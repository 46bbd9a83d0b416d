"""kernels: the flash-attention Pallas calls' (forward, delta, dq, dkv)
share of their roofline, in %."""
from perfbench import readers


def read(rec):
    return readers.roofline_share(rec, "flash_")
