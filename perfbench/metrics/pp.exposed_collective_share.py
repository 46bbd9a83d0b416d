"""pipeline: every device's time inside its step programs in which a
collective (all-reduce, all-gather, reduce-scatter, collective-permute,
all-to-all, with their -start and -done) runs or is in flight and no
other op runs itself, summed over the devices, over the programs' time
summed, in % (the trace)."""
from perfbench import readers


def read(rec):
    return readers.pipeline_share(rec, "exposed_collective")
