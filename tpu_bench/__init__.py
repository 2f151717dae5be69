"""On-chip benchmark of the DYVERSE repo: serving cells and fleet cells.

Run one cell with ``python tpu_bench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>``. Cells, configurations, traffic mixes and
per-layer metric readers are found by name from ``BENCHMARK.json`` and
the files under ``tpu_bench/``.
"""
