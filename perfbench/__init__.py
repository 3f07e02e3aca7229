"""The on-chip benchmark of this repository: ``python3 perfbench/run.py``."""
