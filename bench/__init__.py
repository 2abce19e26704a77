"""Benchmark of the served LUT-network path on a TPU.

``python -m bench.run --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` once and prints one
JSON result line.  See ``PERF.md`` at the root of the repository.
"""
