"""The benchmark's harness: cells, traffic, the served window, the trace
reduction and the comparison that decides ``correct``.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own under ``bench/configs``,
``bench/traffic`` and ``bench/metrics``, found by the name that
``BENCHMARK.json`` gives it; nothing here names a cell.
"""
