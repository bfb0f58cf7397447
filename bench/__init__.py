"""On-chip benchmark of the ScaleGANN build and serving paths.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` on a TPU and prints one JSON result line.
Everything that measures (data generation, the exact reference, the trace
reduction, the peaks table, the work counts and the correctness comparison)
lives in this package; from ``repro`` it takes only the system under test.
"""
