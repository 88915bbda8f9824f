"""On-chip benchmark of DecAFork studies: cells, traffic, reference checks.

Run one cell from the root of a checkout on a machine with a TPU:

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything the harness measures by is kept here, apart from the program:
the configurations (``configs/``), the traffic mixes (``traffic/``), one
reader per metric (``metrics/``), the peaks table (``peaks.json``), the
operation and byte counts (``work.py``), the trace reduction
(``trace.py``) and the plain references that decide ``correct``
(``reference/``). ``program.py`` is the one module that imports the
system under test.
"""
