"""Operations and bytes the work requires, from shapes and run outputs.

These counts do not look at which implementation ran a round (the whole-
round kernel, the unfused stages or the gather family): they count what
any implementation must touch, so a share of a roofline built on them
stays comparable across implementations. All byte counts are computed,
not measured.
"""
from __future__ import annotations

import numpy as np


def walk_round_bytes(config: dict) -> int:
    """Least HBM bytes one active walk costs its node in one round.

    The visited node must read the walk's return-time histogram row,
    ``B' = min(rt_bins, steps)`` int16 bins (no return time can exceed
    the run), and its ``last_seen`` row of ``max_walks`` int32 entries to
    evaluate theta. The operations on them are a few per byte, far below
    the chip's ratio of FLOP/s to bytes/s, so bytes bound the round.
    """
    p = config["protocol"]
    bins = min(p["rt_bins"], config["steps"])
    return 2 * bins + 4 * p["max_walks"]


def active_walk_rounds(studies) -> int:
    """Sum of ``z_t`` over every round of every trajectory of ``studies``
    (``(study, outputs)`` pairs; ``outputs['z']`` is (seeds, steps))."""
    return int(sum(np.asarray(out["z"], np.int64).sum() for _, out in studies))

