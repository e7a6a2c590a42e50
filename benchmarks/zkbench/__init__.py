"""The benchmark's own code: everything a later PR must not be able to
change lives under ``benchmarks/`` (see ``benchmarks/README.md``).

``zkbench`` is imported with ``benchmarks/`` on ``sys.path`` (``run.py``
and the tests put it there), never as ``benchmarks.zkbench``: the test
tree has a ``tests/benchmarks`` directory of its own.
"""
