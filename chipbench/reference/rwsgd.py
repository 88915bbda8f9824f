"""Plain reference of a DecAFork trajectory carrying RW-SGD replicas.

The walks follow ``walks.trajectory``; the payload follows the paper's
learning loop (Sec. I) as the program documents it: every walk starts
from one shared model drawn from the trajectory key; a fork copies the
parent's model and optimizer state into the child's slot before the
round's step; every live walk then takes one AdamW step on a minibatch
of the Markov chain sampled at the node it stands on.

The model is a pre-norm decoder written out in ``jax.numpy``: RMSNorm,
grouped-query causal attention with rotary positions, a SwiGLU MLP and
an untied unembedding, trained on next-token cross entropy. Weights come
from the key as the program draws them (normal, scaled by 1/sqrt(fan-in),
0.02 for the embeddings; norms at 1). Weights, activations and AdamW
state are float32 and matmuls take the platform's default precision, as
the configuration states (on a TPU one bfloat16 pass with float32
accumulation). The controls, one precision step down:
``precision="bfloat16"`` keeps the weights and the activations in
bfloat16 and the walks' theta too; ``"bfloat16-payload"`` the model
alone, with theta as stated.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import draws, walks

INIT_TAG = 0x70AD  # the payload's init key: fold_in(trajectory key, INIT_TAG)


def _normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def init_params(key, m: dict, dtype):
    d, f, V = m["d_model"], m["d_ff"], m["vocab_size"]
    H, KV, hd, L = m["num_heads"], m["num_kv_heads"], m["head_dim"], m["num_layers"]
    k_emb, k_layers = jax.random.split(key)

    def layer(k):
        ks = jax.random.split(k, 6)
        ka = jax.random.split(ks[0], 4)
        km = jax.random.split(ks[3], 3)
        s, so = 1 / math.sqrt(d), 1 / math.sqrt(H * hd)
        return {
            "attn_norm": jnp.ones((d,), dtype),
            "mlp_norm": jnp.ones((d,), dtype),
            "wq": _normal(ka[0], (d, H, hd), s, dtype),
            "wk": _normal(ka[1], (d, KV, hd), s, dtype),
            "wv": _normal(ka[2], (d, KV, hd), s, dtype),
            "wo": _normal(ka[3], (H, hd, d), so, dtype),
            "gate": _normal(km[0], (d, f), s, dtype),
            "up": _normal(km[1], (d, f), s, dtype),
            "down": _normal(km[2], (f, d), 1 / math.sqrt(f), dtype),
        }

    ke = jax.random.split(k_emb, 4)
    return {
        "embed": _normal(ke[0], (V, d), 0.02, dtype),
        "unembed": _normal(ke[1], (d, V), 0.02, dtype),
        "final_norm": jnp.ones((d,), dtype),
        "layers": jax.vmap(layer)(jax.random.split(k_layers, L)),
    }


def _rmsnorm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return y.astype(x.dtype) * scale


def _rope(x, theta):
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1).astype(x.dtype)


def loss_fn(params, tokens, labels, m: dict):
    eps, theta = m["norm_eps"], m["rope_theta"]
    H, KV, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    x = params["embed"][tokens]
    B, S, _ = x.shape
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    for i in range(m["num_layers"]):
        lp = jax.tree.map(lambda a, i=i: a[i], params["layers"])
        h = _rmsnorm(x, lp["attn_norm"], eps)
        q = _rope(jnp.einsum("bsd,dhk->bshk", h, lp["wq"]), theta)
        k = _rope(jnp.einsum("bsd,dhk->bshk", h, lp["wk"]), theta)
        v = jnp.einsum("bsd,dhk->bshk", h, lp["wv"])
        q = q.reshape(B, S, KV, H // KV, hd)
        s = jnp.einsum(
            "bqkgd,btkd->bkgqt", q.astype(jnp.float32), k.astype(jnp.float32)
        ) / math.sqrt(hd)
        w = jax.nn.softmax(jnp.where(causal, s, -1e30), axis=-1)
        a = jnp.einsum("bkgqt,btkd->bqkgd", w.astype(v.dtype), v).reshape(B, S, H, hd)
        x = x + jnp.einsum("bshk,hkd->bsd", a, lp["wo"])
        h = _rmsnorm(x, lp["mlp_norm"], eps)
        g = jax.nn.silu(h @ lp["gate"]) * (h @ lp["up"])
        x = x + g @ lp["down"]
    logits = (_rmsnorm(x, params["final_norm"], eps) @ params["unembed"]).astype(jnp.float32)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)


def sample_batch(logits, key, batch: int, seq: int, node):
    """``batch`` sequences of ``seq + 1`` tokens of the chain at ``node``:
    a uniform start, then each token drawn from the row of the last."""
    k0, kseq = jax.random.split(jax.random.fold_in(key, node))
    tok = jax.random.randint(k0, (batch,), 0, logits.shape[0])
    seqs = [tok]
    for k in jax.random.split(kseq, seq):
        tok = jax.random.categorical(k, logits[tok])
        seqs.append(tok)
    full = jnp.stack(seqs, axis=1).astype(jnp.int32)
    return full[:, :-1], full[:, 1:]


def _adamw(p, g, mu, nu, step, opt):
    """One AdamW update of one leaf (float32 arithmetic, weight decay 0)."""
    b1, b2 = opt["b1"], opt["b2"]
    g = g.astype(jnp.float32)
    mu = b1 * mu + (1 - b1) * g
    nu = b2 * nu + (1 - b2) * g * g
    c1 = 1.0 - b1 ** step.astype(jnp.float32)
    c2 = 1.0 - b2 ** step.astype(jnp.float32)
    delta = (mu / c1) / (jnp.sqrt(nu / c2) + opt["eps"])
    return (p.astype(jnp.float32) - opt["lr"] * delta).astype(p.dtype), mu, nu


@functools.partial(jax.jit, static_argnames=("spec",))
def _round(state, pos, active, key, logits, *, spec):
    m, opt, batch, seq = (dict(x) if isinstance(x, tuple) else x for x in spec)
    params, mu, nu, step = state

    def one(p, mu, nu, step, node, live):
        tokens, labels = sample_batch(logits, key, batch, seq, node)
        loss, grads = jax.value_and_grad(loss_fn)(p, tokens, labels, m)
        new = jax.tree.map(
            lambda p, g, a, b: _adamw(p, g, a, b, step + 1, opt), p, grads, mu, nu
        )
        pick = lambda i, old: jax.tree.map(  # noqa: E731
            lambda n, o: jnp.where(live, n[i], o), new, old,
            is_leaf=lambda x: isinstance(x, tuple),
        )
        return (
            pick(0, p), pick(1, mu), pick(2, nu),
            step + live.astype(step.dtype), jnp.where(live, loss, 0.0),
        )

    p, mu, nu, step, losses = jax.vmap(one)(params, mu, nu, step, pos, active)
    return (p, mu, nu, step), losses


@jax.jit
def _fork(state, src):
    return jax.tree.map(lambda a: a[src], state)


def trajectory(neighbors, proto, config, base_key, seeds, index, task, precision="float32"):
    """``walks.trajectory`` outputs plus, over every round, ``trained``
    (live walks, each of which takes one local step) and ``fork_pairs``
    ((round, child slot, parent slot) of every fork: in that round the
    child holds a copy of the parent's replica and stands on the
    parent's node, so both take their step on the same batch); and
    ``loss`` and ``trained_mask``, (rounds, W), of the replicas over the
    first ``config["check"]["loss_rounds"]`` rounds: the losses of the
    initial model and of its first local steps. Later rounds are not
    replayed: AdamW turns rounding noise in near-zero gradients into
    whole steps, so two correct runs drift apart as training goes on."""
    p = config["payload"]
    m = p["model"]
    W = proto["max_walks"]
    low_model = precision in ("bfloat16", "bfloat16-payload")
    dtype = jnp.bfloat16 if low_model else jnp.float32
    key, _, k_run = draws.trajectory_key(base_key, seeds, index)
    params = init_params(jax.random.fold_in(key, INIT_TAG), m, dtype)
    stack = lambda x: jnp.broadcast_to(x, (W,) + x.shape)  # noqa: E731
    zeros = jax.tree.map(lambda x: jnp.zeros((W,) + x.shape, jnp.float32), params)
    state = [(jax.tree.map(stack, params), zeros, zeros, jnp.zeros((W,), jnp.int32))]
    spec = (
        tuple(sorted(m.items())), tuple(sorted(p["optimizer"].items())),
        p["local_batch"], p["seq_len"],
    )
    logits = jnp.asarray(task)
    losses, masks, trained, pairs = [], [], [], []

    rounds = config["check"]["loss_rounds"]

    def on_round(t, pos, active, fork_parent):
        trained.append(int(active.sum()))
        pairs.extend((t, int(c), int(fork_parent[c])) for c in np.flatnonzero(fork_parent >= 0))
        if t >= rounds:
            return
        if (fork_parent >= 0).any():
            src = np.where(fork_parent >= 0, fork_parent, np.arange(W))
            state[0] = _fork(state[0], jnp.asarray(src))
        k_visit = draws.fold(k_run, draws.VISIT, jnp.int32(t))
        state[0], loss = _round(
            state[0], jnp.asarray(pos, jnp.int32), jnp.asarray(active), k_visit,
            logits, spec=spec,
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
