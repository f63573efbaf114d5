"""The chip benchmark: ``python3 -m bench.run --workload <cell> --seed <n>
--seconds <s> --trace <0|1>`` runs one cell of ``BENCHMARK.json`` once.

Everything that measures lives here, apart from the program it measures:
traffic generation (``generate``), the plain references (``reference``),
the systems driven (``systems``), clocks and spans (``timing``), the
reduction of a profiler trace (``trace``), one reader per metric
(``metrics/``), the controls (``control``) and the table of chip peaks
(``peaks.json``).
"""
