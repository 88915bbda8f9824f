"""Model FLOP/s utilisation of the learning payload: FLOPs of the local
steps live replicas took in the traced window (``work.transformer_step_flops``
times the payload's ``trained`` counts) over the window's seconds times
the chip's bf16 peak, in %."""
from chipbench import work


def read(rec):
    p = rec.config.get("payload")
    if not p or rec.trace is None or rec.peaks is None:
        return None
    steps = sum(int(out["trained"].sum()) for _, out in rec.studies)
    if not steps:
        return None
    flops = steps * work.transformer_step_flops(p["model"], p["local_batch"], p["seq_len"])
    return 100.0 * flops / (rec.trace.window_s * rec.peaks["bf16_flops_per_s"])
