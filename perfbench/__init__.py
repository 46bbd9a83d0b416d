"""The chip benchmark of the SPB trainer (see ``BENCHMARK.json``).

Run one cell with ``python3 perfbench/run.py --workload <name> --seed <n>
--seconds <s> --trace <0|1>`` from the root of a checkout on a TPU host.
"""
