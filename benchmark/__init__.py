"""The yardstick: cells, traffic, references, counts and trace reduction.

Nothing here is imported by ``rocket_tpu``; the benchmark imports the
program only as the system under test (``benchmark/kinds/*.py``).
"""
