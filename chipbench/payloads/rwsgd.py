"""RW-SGD: every walk carries a dense decoder replica trained by AdamW.

The payload of the paper's motivating case (Sec. I), on the benchmark's
side: the learning task it hands the program, the program's
``RwSgdPayload`` over it, the FLOPs of one local step, and the faults a
run can plant in it. Its reference is ``reference/rwsgd.py``.

Each graph node samples its local sequences from one fixed first-order
Markov chain over the vocabulary (the program's ``data.synthetic``
sampler, keyed per node). The chain's transition logits are an input,
like the graph: made here on the host from the ``task`` entry and handed
to both the program and the reference.
"""
from __future__ import annotations

import numpy as np


def inputs(payload: dict) -> np.ndarray:
    """(V, V) float32 logits ``U V^T / sqrt(rank) * temperature`` with
    standard normal ``U`` (V, rank) and ``V`` (rank, V)."""
    t = payload["task"]
    vocab = payload["model"]["vocab_size"]
    rng = np.random.default_rng(t["seed"])
    u = rng.standard_normal((vocab, t["rank"]))
    v = rng.standard_normal((t["rank"], vocab))
    return (u @ v / np.sqrt(t["rank"]) * t["temperature"]).astype(np.float32)


def build(config: dict, task: np.ndarray):
    """``RwSgdPayload`` for the configuration's ``payload`` entry, over the
    transition table ``inputs`` made (``task``: float32 (V, V))."""
    import jax

    from repro.data.synthetic import SyntheticTask
    from repro.models.config import ModelConfig
    from repro.models.model import Model
    from repro.optim import RwSgdPayload, adamw

    p = config["payload"]
    model = ModelConfig(**p["model"])
    opt = p["optimizer"]
    return RwSgdPayload(
        Model(model),
        adamw(opt["lr"], b1=opt["b1"], b2=opt["b2"], eps=opt["eps"]),
        SyntheticTask(logits=jax.numpy.asarray(task), entropy=float("nan")),
        max_walks=config["protocol"]["max_walks"],
        local_batch=p["local_batch"],
        seq_len=p["seq_len"],
    )


def step_flops(payload: dict) -> int:
    """FLOPs of one training step (forward and backward, 3x the forward)
    of the dense GQA decoder ``payload["model"]`` on ``local_batch`` x
    ``seq_len`` tokens.

    Forward per token: 2 per weight of every matmul (q, k, v, o, the three
    SwiGLU projections, the unembedding). Causal attention per sequence,
    layer and head: 2 x head_dim for each of the seq (seq + 1) / 2 (query,
    key) pairs with the key at or before the query, for the scores and as
    much for the weighted values. The embedding lookup is no matmul and
    counts nothing.
    """
    model, batch, seq = payload["model"], payload["local_batch"], payload["seq_len"]
    d, f, L, V = model["d_model"], model["d_ff"], model["num_layers"], model["vocab_size"]
    H, KV, hd = model["num_heads"], model["num_kv_heads"], model["head_dim"]
    per_layer = d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * f
    matmul = 2 * (L * per_layer + d * V) * batch * seq
    attn = 2 * H * hd * seq * (seq + 1) * L * batch
    return 3 * (matmul + attn)


def faults() -> dict:
    """The sweep's three faults in the replicas' step, and two of the
    payload's own: a fork that leaves the child's replica uncopied, and a
    step count that counts dead slots as trained."""
    from repro.models.model import Model
    from repro.optim import rw_sgd

    make = rw_sgd.replica_train_step
    loss = Model.loss
    visit = rw_sgd.RwSgdPayload.on_visit

    def unchanged_state(loss_fn, optimizer):
        train = make(loss_fn, optimizer)
        return lambda rs, batches, active: (rs, train(rs, batches, active)[1])

    def half_batch(self, params, batch):
        return loss(self, params, {k: v[: v.shape[0] // 2] for k, v in batch.items()})

    def altered_answer(self, rs, walks, t, key):
        rs, out = visit(self, rs, walks, t, key)
        return rs, out._replace(loss=out.loss * 1.01)

    def uncopied_fork(self, rs, fork_parent):
        return rs

    def miscounted_steps(self, rs, walks, t, key):
        rs, out = visit(self, rs, walks, t, key)
        return rs, out._replace(trained=out.trained * 0 + out.loss.shape[0])

    return {
        "unchanged_state": [(rw_sgd, "replica_train_step", unchanged_state)],
        "half_batch": [(Model, "loss", half_batch)],
        "altered_answer": [(rw_sgd.RwSgdPayload, "on_visit", altered_answer)],
        "uncopied_fork": [(rw_sgd.RwSgdPayload, "on_fork", uncopied_fork)],
        "miscounted_steps": [(rw_sgd.RwSgdPayload, "on_visit", miscounted_steps)],
    }
