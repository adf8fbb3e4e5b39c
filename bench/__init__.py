"""On-chip benchmark of the warehouse: SSB traffic through ``repro.api``.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1``
runs one cell of ``BENCHMARK.json``.  Everything the benchmark measures
with lives here: the datasets (``ssb``: SSB's generator, queries and
reference keys), the traffic generator (``loadgen``), the sqlite3
reference and the comparison (``reference``), the profiler-trace reduction
(``profiling``), the kernels' interface bytes (``kernel_bytes``) and the
chips' peaks (``peaks``).  Configurations, traffic mixes and per-layer
metric readers are files found by name under ``configs/``, ``traffic/``
and ``metrics/``; a configuration's ``"dataset"`` names its dataset module,
``<dataset>.py`` here.
"""
