"""pipeline: every device's time inside its step programs in which no op
runs nor is in flight, summed over the devices, over the programs' time
summed, in % (the trace; the split by stage is in the breakdown)."""
from perfbench import readers


def read(rec):
    return readers.pipeline_share(rec, "idle")
