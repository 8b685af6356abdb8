"""The on-chip benchmark of this repository (``BENCHMARK.json`` at the root).

``python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell once and prints one JSON result line. See
``run.py`` for the flow and ``PERF.md`` for the cells and their metrics.
Everything the yardstick needs lives here: traffic generators, weight
generation, the plain references, FLOP/byte counts, the peak table and the
reduction from a profiler trace to metrics.
"""
