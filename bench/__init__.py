"""On-chip benchmark of the sweep simulator.

Run a cell with ``python bench/run.py``; ``BENCHMARK.json`` at the
repository root lists the cells and metrics.
"""
