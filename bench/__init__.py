"""Chip benchmark of the training and serving stack.

``python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` on the TPU it is started on and prints
one JSON result line.  Everything the yardstick needs (traffic, weights,
operation and byte counts, trace reduction, the plain reference and the
limits that decide ``correct``) lives in this directory; from the program it
takes only the system under test.
"""
