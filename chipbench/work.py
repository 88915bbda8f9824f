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


def transformer_step_flops(model: dict, batch: int, seq: int) -> int:
    """FLOPs of one training step (forward and backward, 3x the forward)
    of the dense GQA decoder ``model`` on ``batch`` x ``seq`` tokens.

    Forward per token: 2 per weight of every matmul (q, k, v, o, the three
    SwiGLU projections, the unembedding). Causal attention per sequence,
    layer and head: 2 x head_dim for each of the seq (seq + 1) / 2 (query,
    key) pairs with the key at or before the query, for the scores and as
    much for the weighted values. The embedding lookup is no matmul and
    counts nothing.
    """
    d, f, L, V = model["d_model"], model["d_ff"], model["num_layers"], model["vocab_size"]
    H, KV, hd = model["num_heads"], model["num_kv_heads"], model["head_dim"]
    per_layer = d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * f
    matmul = 2 * (L * per_layer + d * V) * batch * seq
    attn = 2 * H * hd * seq * (seq + 1) * L * batch
    return 3 * (matmul + attn)
