"""The benchmark's own tests: ``benchmarks/`` goes on ``sys.path`` so that
``zkbench`` imports as it does under ``benchmarks/run.py``."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "benchmarks")
for path in (BENCH_DIR, ROOT):
    if path not in sys.path:
        sys.path.insert(0, path)
