"""The on-chip benchmark of the permutation engine's served crypto paths.

``python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json``.  Everything that
belongs to one configuration, traffic mix or per-layer metric lives in
a file of its own and is found by name (see ``spec``).
"""
