"""Chip benchmark of the elastic fleet executor.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` on the chip it is started on.  Each
cell's configuration, traffic mix, correctness limits and per-layer metric
readers are files of their own, found by name (``spec.py``).
"""
