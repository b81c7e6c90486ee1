"""The benchmark's harness: everything the yardstick is made of.

Nothing here names a cell, a configuration or a metric: those are data
files under ``benchmark/`` that ``manifest`` finds by the names in
``BENCHMARK.json``.
"""
