"""On-chip benchmark of the served multi-adapter path (``python3 -m
bench.run``); see ``BENCHMARK.json`` and ``PERF.md``."""
