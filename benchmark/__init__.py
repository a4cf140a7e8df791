"""The benchmark of tpu-r2d2: one data-driven harness, run by the driver as
``python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by the name ``BENCHMARK.json``
gives it (``configs/``, ``traffic/``, ``layer_metrics/``, ``reference/``);
the code here is the yardstick that later PRs may add to but not edit.
"""
