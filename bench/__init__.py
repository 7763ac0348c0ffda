"""On-chip benchmark of iPDB's semantic-SQL serving path.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json``.  Everything the benchmark measures with
(traffic generation, the reduction from traces and counters to metrics, the
peaks table, operation and byte counts, the plain reference model and the
comparison that decides ``correct``) lives in this package; from the program
it takes only the system under test, its counters and its program names.
"""
