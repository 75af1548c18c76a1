"""Chip benchmark of the ComPEFT zero-merge serving path.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once.  Everything the benchmark
measures with lives here: the traffic generator, the seeded weights, the
plain float32 reference and the comparison that decides ``correct``, the
trace reduction, the kernel cost functions and the table of peaks.  The
program under test (``src/repro``) supplies only the serving engine.
"""
