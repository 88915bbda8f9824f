"""On-chip benchmark of DecAFork studies: cells, traffic, reference checks.

Run one cell from the root of a checkout on a machine with a TPU:

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything the harness measures by is kept here, apart from the program:
the configurations (``configs/``), the traffic mixes (``traffic/``), one
reader per metric (``metrics/``), the peaks table (``peaks.json``), the
byte counts of a round (``work.py``), the trace reduction
(``trace.py``), the plain references that decide ``correct``
(``reference/``), and each learning payload's kind: its task, builder,
FLOPs a step and faults (``payloads/``), with its reference beside the
others. ``program.py``, the kinds' builders and the planted faults
(``faults.py``) are what imports the system under test.
"""
