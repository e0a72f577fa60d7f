"""perfbench's own yardstick: everything a later PR may not change.

Nothing here names a cell, a configuration, a traffic mix or a metric:
those are files found by the names ``BENCHMARK.json`` gives.
"""
