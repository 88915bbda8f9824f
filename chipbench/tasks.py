"""The learning payload's data: a Markov chain the benchmark makes itself.

Each graph node samples its local sequences from one fixed first-order
chain over the vocabulary (the program's ``data.synthetic`` sampler,
keyed per node). The chain's transition logits are an input, like the
graph: made here on the host from the configuration's ``task`` entry and
handed to both the program and the reference.
"""
from __future__ import annotations

import numpy as np


def make(payload: dict) -> np.ndarray:
    """(V, V) float32 logits ``U V^T / sqrt(rank) * temperature`` with
    standard normal ``U`` (V, rank) and ``V`` (rank, V)."""
    t = payload["task"]
    vocab = payload["model"]["vocab_size"]
    rng = np.random.default_rng(t["seed"])
    u = rng.standard_normal((vocab, t["rank"]))
    v = rng.standard_normal((t["rank"], vocab))
    return (u @ v / np.sqrt(t["rank"]) * t["temperature"]).astype(np.float32)
