"""The on-chip benchmark of the DAMOV characterization pipeline.

``python3 bench/run.py --workload <name> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json``; see :mod:`bench.run`.
"""
