"""The on-chip benchmark: a harness driven by ``BENCHMARK.json``.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Configurations (``configs/``), traffic mixes (``traffic/``) and per-layer
metric readers (``metrics/``) are found by the names in ``BENCHMARK.json``.
"""
