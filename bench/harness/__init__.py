"""The benchmark's own code: what every PR is measured with.

Nothing here is imported by the program under test. The harness reads its
cells, configurations, traffic mixes and per-layer metrics by name from
``BENCHMARK.json`` and the files beside it (see ``spec.py``).
"""
