"""Model FLOP/s utilisation of the learning payload: FLOPs of the local
steps live replicas took in the traced window (the payload kind's
``step_flops``, ``payloads/<kind>.py``, times the payload's ``trained``
counts) over the window's seconds times the chip's bf16 peak, in %."""


def read(rec):
    if rec.kind is None or rec.trace is None or rec.peaks is None:
        return None
    steps = sum(int(out["trained"].sum()) for _, out in rec.studies)
    if not steps:
        return None
    flops = steps * rec.kind.step_flops(rec.config["payload"])
    return 100.0 * flops / (rec.trace.window_s * rec.peaks["bf16_flops_per_s"])
