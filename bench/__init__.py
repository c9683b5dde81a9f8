"""Chip benchmark of the LiLIS serve path (see BENCHMARK.json and PERF.md).

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell on the TPU it is started on and prints one
JSON result line. Configurations (``configs/``), traffic mixes
(``traffic/``) and per-layer metric readers (``metrics/``) are found by
the names BENCHMARK.json gives them, so a new cell needs only new files.
"""
