"""Benchmark of the serving system on the chip: see ``run.py``."""
