"""END-TO-END DRIVER: decentralized RW-SGD learning with DECAFORK(+).

This is the paper's full system in one *fused, compiled* call:

  * a graph of data-holding nodes (each owns a Markov-chain shard);
  * Z_0 random walks, each carrying a model replica + optimizer state;
  * every round, each live walk takes a local SGD step on the data of
    the node it sits on, then hops to a random neighbor (RW-SGD);
  * nodes run DECAFORK: estimate the live-walk count from return-time
    survival, fork the visiting walk (replica duplicated!) when the
    estimate drops, terminate when it overshoots (DECAFORK+);
  * a burst failure kills several walks mid-training — the system
    detects it, re-forks, and learning continues without losing the
    surviving replicas' progress.

The learning workload is an ``RwSgdPayload`` plugged into one declarative
``repro.api.Experiment``: model forks, local SGD steps and loss telemetry
all run inside the trajectory's single ``lax.scan`` — the whole training
run is ONE jitted device call, not a Python per-hop loop.

Run:  PYTHONPATH=src python examples/decentralized_training.py
      [--nodes 64 --z0 6 --steps 1400 --burst-at 900 --burst-size 3]
"""
import argparse
import time

import jax
import numpy as np

from repro.api import Experiment
from repro.configs import get_smoke_config
from repro.core.failures import FailureConfig
from repro.core.protocol import ProtocolConfig
from repro.data import make_markov_task
from repro.graphs import random_regular_graph
from repro.models.model import Model
from repro.optim import RwSgdPayload, adamw
from repro.utils.compile_cache import use_compile_cache


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=64)
    ap.add_argument("--degree", type=int, default=8)
    ap.add_argument("--z0", type=int, default=6)
    ap.add_argument("--max-walks", type=int, default=16)
    ap.add_argument("--steps", type=int, default=1400)
    ap.add_argument("--burst-at", type=int, default=900)
    ap.add_argument("--burst-size", type=int, default=3)
    ap.add_argument("--protocol-start", type=int, default=400)
    ap.add_argument("--eps", type=float, default=1.2)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--local-batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--train-every", type=int, default=1,
                    help="walk hops per local SGD step")
    args = ap.parse_args()

    # --- the decentralized system --------------------------------------
    g = random_regular_graph(args.nodes, args.degree, seed=0)
    pcfg = ProtocolConfig(
        algorithm="decafork", z0=args.z0, max_walks=args.max_walks,
        eps=args.eps, protocol_start=args.protocol_start, rt_bins=512,
    )
    fcfg = FailureConfig(burst_times=(args.burst_at,), burst_sizes=(args.burst_size,))

    # --- the learning payload ------------------------------------------
    cfg = get_smoke_config("paper_rwsgd")
    model = Model(cfg)
    task = make_markov_task(cfg.vocab_size)
    payload = RwSgdPayload(
        model, adamw(args.lr), task, max_walks=args.max_walks,
        local_batch=args.local_batch, seq_len=args.seq,
        train_every=args.train_every,
    )
    n_params = sum(
        x.size for x in jax.tree.leaves(model.init(jax.random.key(0)))
    )
    print(f"graph n={g.n} d={args.degree} | Z0={args.z0} walks | "
          f"payload {cfg.name} ({n_params:,} params/replica) | "
          f"entropy floor {task.entropy:.3f}")

    # --- the whole trajectory: ONE fused compiled call ------------------
    t0 = time.time()
    (final, replicas), (outs, learn) = Experiment(
        graph=g, protocol=pcfg, failures=fcfg, steps=args.steps,
        payload=payload,
    ).run(key=0)
    jax.block_until_ready(learn.mean_loss)
    wall = time.time() - t0

    z = np.asarray(outs.z)
    loss = np.asarray(learn.mean_loss)
    trained = np.asarray(learn.trained) > 0  # rounds where a step ran

    def loss_over(window: slice) -> float:
        """Mean loss over the window's *training* rounds only (with
        --train-every > 1 the off rounds report 0, not a loss)."""
        w = loss[window][trained[window]]
        return float(w.mean()) if w.size else float("nan")

    for t in range(0, args.steps, 100):
        marker = "  <-- BURST" if args.burst_at in range(t, t + 100) else ""
        print(f"t={t:5d}  Z={z[t]:2d}  "
              f"loss={loss_over(slice(t, t + 100)):.3f}{marker}")

    pre = slice(max(args.burst_at - 100, 0), args.burst_at)
    post = slice(args.steps - 100, args.steps)
    print("\n=== summary ===")
    print(f"wall: {wall:.1f}s for {args.steps} fused rounds "
          f"({wall * 1e3 / args.steps:.2f} ms/round incl. compile)")
    print(f"Z before burst: {z[pre].mean():.1f}   Z at end: {z[post].mean():.1f}")
    print(f"loss before burst: {loss_over(pre):.3f} -> end: {loss_over(post):.3f} "
          f"(floor {task.entropy:.3f})")
    print(f"replica local-step counters: {np.asarray(replicas.steps).tolist()}")
    survived = (z > 0).all()
    print(f"resilience: {'OK — at least one walk alive throughout' if survived else 'FAILED'}")


if __name__ == "__main__":
    main()
