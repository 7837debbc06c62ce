"""On-chip benchmark of the federated round and the serving engine.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1``
runs one cell of ``BENCHMARK.json`` once and prints one JSON result line.
Everything that measures lives here: traffic generation, the plain float32
reference that decides ``correct``, the FLOP and byte counters, the peaks
table and the reduction of a profiler trace to metrics.
"""
