"""On-chip benchmark of the federated bilevel train step.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once and prints one JSON result line.
Everything a cell needs is found by name: its configuration under
``bench/configs/``, its traffic under ``bench/traffic/``, its limits under
``bench/limits/``, the FLOP model and plain reference of its model family
under ``bench/flops/`` and ``bench/reference/``, and one reader per
per-layer metric under ``bench/layers/``.
"""
