"""The benchmark: one run of one cell is ``python3 perfbench/run.py``."""
