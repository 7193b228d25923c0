"""The benchmark's own data generators, frozen copies of the program's.

A later change to ``repro.data.synthetic`` or to the median-gamma
heuristic in ``repro.core.kernel_fns`` cannot move what the benchmark
generates: these copies are part of the yardstick.
"""
