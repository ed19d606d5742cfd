"""Fixtures of the benchmark's CPU tests (see ``bench_tiny``)."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from bench_tiny import tiny_registry  # noqa: E402,F401
