"""What a run hands to each metric reader (``metrics/<name>.py``).

A reader is ``read(rec: Record) -> float | None``: it returns None where
the run holds nothing for it to read, and the harness then leaves the
metric out of the line.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass
class Record:
    config: dict  # the cell's configuration file
    setup_s: float  # process start to window start, host clock
    window_s: float  # first study dispatched to last study on the host
    studies: list  # (studies.Study, outputs by field) finished in the window
    compile: dict  # clock.EVENTS seen during set-up: {event: (count, seconds)}
    peaks: dict | None  # the device's row of peaks.json (None off the chip)
    trace: object = None  # trace.Summary of the window (--trace 1 only)
    kind: object = None  # payloads.Kind of the config's payload (None: walks alone)
