"""Chip benchmark of BCPNN training: ``python bench/run.py --workload <cell>``.

Everything that measures lives here and imports nothing of the program but
its public API: the traffic generator (``data``), the table of peaks
(``peaks``), the work function (``work``), the float32 reference
(``reference``), the comparison that decides ``correct`` (``check``) and the
reduction of a profiler trace (``devtrace``).  Cells, configurations, traffic
mixes and per-layer metrics are files found by the names in
``BENCHMARK.json`` (``spec``).
"""
