"""ACAI's chip benchmark: cells, traffic, reference and trace reduction.

``bench/run.py`` is the command; ``BENCHMARK.json`` at the root of the
repository lists the configurations, cells and metrics that it runs.
"""
