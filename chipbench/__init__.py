"""Chip benchmark of the GROUP BY engine: TPC-H aggregates on a TPU.

``python chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` once and prints one
JSON result line.  Configurations, traffic mixes and per-layer metrics are
files found by the names ``BENCHMARK.json`` gives them.
"""
