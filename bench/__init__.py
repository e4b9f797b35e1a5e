"""Chip benchmark of the sparse engine: one cell, one run, one result line.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
(see ``bench/README.md``).  Cells, configurations, traffic mixes and
per-layer metrics are data: ``BENCHMARK.json`` names them and the harness
finds each by its name under this directory.
"""
