"""The chip benchmark of Sector/Sphere: harness, cells, trace reduction.

``python bench/run.py --workload <name> --seed <n> --seconds <s> --trace 0|1``
runs one cell once. Everything a cell needs is found by name under this
directory (see :mod:`bench.spec`).
"""
