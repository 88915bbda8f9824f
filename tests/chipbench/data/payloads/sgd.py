"""A payload kind kept outside ``chipbench/``, found only through the
loader's directory arguments: walks carry replicas of a dense decoder
trained by plain SGD (``repro.optim.sgd``), on the ``rwsgd`` kind's
Markov task. Its reference is ``../reference/sgd.py``."""
from chipbench.payloads.rwsgd import inputs  # noqa: F401  the same task


def build(config, task):
    import jax

    from repro.data.synthetic import SyntheticTask
    from repro.models.config import ModelConfig
    from repro.models.model import Model
    from repro.optim import RwSgdPayload, sgd

    p = config["payload"]
    return RwSgdPayload(
        Model(ModelConfig(**p["model"])),
        sgd(p["optimizer"]["lr"]),
        SyntheticTask(logits=jax.numpy.asarray(task), entropy=float("nan")),
        max_walks=config["protocol"]["max_walks"],
        local_batch=p["local_batch"],
        seq_len=p["seq_len"],
    )


def step_flops(payload):
    """6 FLOPs per weight of the matmuls and token (forward and backward),
    attention scores left out."""
    m = payload["model"]
    d, f, H, KV, hd = m["d_model"], m["d_ff"], m["num_heads"], m["num_kv_heads"], m["head_dim"]
    weights = m["num_layers"] * (2 * d * H * hd + 2 * d * KV * hd + 3 * d * f) + d * m["vocab_size"]
    return 6 * weights * payload["local_batch"] * payload["seq_len"]


def faults():
    return {}
