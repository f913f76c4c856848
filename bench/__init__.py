"""Chip benchmark for the serving path, driven by data.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1``
runs one cell of ``BENCHMARK.json`` once on the accelerator it finds and
prints one JSON result line.  Every cell is assembled from files found by
name: a configuration under ``bench/configs/``, the architecture its
``model_type`` names under ``bench/archs/``, a traffic mix under
``bench/traffic/`` and one reader per metric under ``bench/metrics/``.
"""
