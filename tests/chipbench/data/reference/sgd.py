"""Plain reference of the ``sgd`` fixture kind: the walks, model and
batches of ``chipbench/reference/rwsgd.py``, each live replica taking one
plain SGD step, ``p - lr * g``, a round."""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import draws, walks
from chipbench.reference.rwsgd import INIT_TAG, init_params, loss_fn, sample_batch


@functools.partial(jax.jit, static_argnames=("spec",))
def _round(params, pos, active, key, logits, *, spec):
    m, lr, batch, seq = spec

    def one(p, node, live):
        tokens, labels = sample_batch(logits, key, batch, seq, node)
        loss, grads = jax.value_and_grad(loss_fn)(p, tokens, labels, dict(m))
        p = jax.tree.map(lambda a, g: jnp.where(live, a - lr * g.astype(a.dtype), a), p, grads)
        return p, jnp.where(live, loss, 0.0)

    return jax.vmap(one)(params, pos, active)


def trajectory(neighbors, proto, config, base_key, seeds, index, inputs, precision="float32"):
    p = config["payload"]
    m, W = p["model"], proto["max_walks"]
    dtype = jnp.bfloat16 if precision in ("bfloat16", "bfloat16-payload") else jnp.float32
    key, _, k_run = draws.trajectory_key(base_key, seeds, index)
    init = init_params(jax.random.fold_in(key, INIT_TAG), m, dtype)
    params = [jax.tree.map(lambda x: jnp.broadcast_to(x, (W,) + x.shape), init)]
    spec = (tuple(sorted(m.items())), p["optimizer"]["lr"], p["local_batch"], p["seq_len"])
    losses, masks, trained, pairs = [], [], [], []

    def on_round(t, pos, active, fork_parent):
        trained.append(int(active.sum()))
        pairs.extend((t, int(c), int(fork_parent[c])) for c in np.flatnonzero(fork_parent >= 0))
        if t >= config["check"]["loss_rounds"]:
            return
        src = np.where(fork_parent >= 0, fork_parent, np.arange(W))
        params[0] = jax.tree.map(lambda a: a[src], params[0])
        k_visit = draws.fold(k_run, draws.VISIT, jnp.int32(t))
        params[0], loss = _round(
            # copies: the walks mutate ``active`` in place while the step runs
            params[0], jnp.asarray(pos, jnp.int32), jnp.array(active, copy=True), k_visit,
            jnp.asarray(inputs), spec=spec,
        )
        losses.append(loss)
        masks.append(active.copy())

    out = walks.trajectory(
        neighbors, proto, config["failures"], config["steps"],
        base_key, seeds, index, "bfloat16" if precision == "bfloat16" else "float32",
        on_round=on_round,
    )
    out["loss"] = np.asarray(jnp.stack(losses))
    out["trained_mask"] = np.stack(masks)
    out["trained"] = np.asarray(trained, np.int64)
    out["fork_pairs"] = np.asarray(pairs, np.int64).reshape(-1, 3)
    return out
