"""On-chip benchmark of the PNPCoin chain: one cell per run.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``

Everything that measures lives here: the closed-loop block driver
(``harness.py``), one system driver per configuration kind
(``systems/``), the plain references that decide ``correct``
(``reference/``), the trace reduction (``trace.py``), the peak table
(``peaks.py``), the model FLOP count (``flops.py``) and one reader per
metric (``metrics/<name>.py``).  Cells, configurations and traffic mixes
are data (``BENCHMARK.json``, ``configs/``, ``traffic/``, ``cells/``),
found by the names in ``BENCHMARK.json``.
"""
