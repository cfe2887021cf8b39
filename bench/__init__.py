"""Chip benchmark of the wave queue: one harness, driven by data.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` once.  A cell's
configuration (``configs/``), traffic mix (``traffic/``), reference
(``reference/``) and per-layer metrics (``metrics/``) are files found by
the names ``BENCHMARK.json`` gives them; nothing here names one.
"""
