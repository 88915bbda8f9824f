"""Share of the round's roofline: the least time the chip needs for the
bytes every round must read (``work.walk_round_bytes`` per active walk,
summed over ``z_t`` of the traced window's studies, at the HBM peak),
over the device's busy time in the traced window, in %. Computed from
shapes and outputs, so it counts the same work whatever implements the
round."""
from chipbench import work


def read(rec):
    if rec.trace is None or rec.peaks is None or not rec.trace.busy_s:
        return None
    walk_rounds = work.active_walk_rounds(rec.studies)
    if not walk_rounds:
        return None
    least_s = walk_rounds * work.walk_round_bytes(rec.config) / rec.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / rec.trace.busy_s
