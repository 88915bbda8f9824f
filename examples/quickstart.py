"""Quickstart: train a small decoder LM on the synthetic Markov task.

Demonstrates the public API end to end on one host:
  config -> Model -> optimizer -> jitted train step -> checkpoint.

Run:  PYTHONPATH=src python examples/quickstart.py [--steps 120]
The loss should fall from ~ln(V) toward the task's entropy floor.
"""
import argparse
import time

import jax

from repro.checkpoint import save_pytree
from repro.configs import get_config
from repro.data import make_markov_task, sample_batch
from repro.launch.train import make_train_step
from repro.models.model import Model
from repro.optim import adamw, cosine_schedule
from repro.utils.compile_cache import use_compile_cache


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=120)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt", default="/tmp/repro_quickstart.npz")
    args = ap.parse_args()

    cfg = get_config("paper_rwsgd")  # the paper's small payload LM
    model = Model(cfg)
    task = make_markov_task(cfg.vocab_size)
    opt = adamw(cosine_schedule(3e-3, warmup=10, total=args.steps))

    key = jax.random.key(0)
    params = model.init(key)
    opt_state = opt.init(params)
    step = jax.jit(make_train_step(model, opt))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"model={cfg.name} params={n_params:,} "
          f"entropy floor={task.entropy:.3f} nats/token")

    t0 = time.time()
    for i in range(args.steps):
        batch = sample_batch(task, jax.random.fold_in(key, i), args.batch, args.seq)
        params, opt_state, metrics = step(params, opt_state, batch)
        if i % 10 == 0 or i == args.steps - 1:
            print(f"step {i:4d}  loss {float(metrics['loss']):.4f}  "
                  f"({time.time() - t0:5.1f}s)")

    save_pytree(args.ckpt, params, metadata={"arch": cfg.name, "steps": args.steps})
    print(f"checkpoint saved to {args.ckpt}")
    final = float(metrics["loss"])
    print(f"final loss {final:.3f} vs floor {task.entropy:.3f} "
          f"(gap {final - task.entropy:+.3f})")


if __name__ == "__main__":
    main()
