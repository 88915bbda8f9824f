"""Random-walk SGD: the paper's learning algorithm (Section I).

Each live walk carries a model replica; the currently visited node takes a
local (mini-batch) SGD step on *its own* data shard and forwards the
replica. Replicas live in a fixed-capacity stack with a leading walk-slot
axis — forking a walk is a slot-to-slot copy of (params, opt moments),
which is exactly DECAFORK's "identical duplicate" semantics, and
termination simply deactivates the slot.

``replica_train_step`` vectorizes the per-walk local step with ``vmap``
so one jitted call advances every live replica simultaneously (the
synchronous-round semantics of the simulator). :class:`RwSgdPayload`
packages the whole thing as a ``core.payload.Payload``, fusing RW-SGD
into the simulator's ``lax.scan`` — learning runs *inside* the compiled
trajectory, batches under ``Experiment.ensemble``/``.sweep``
(``repro.api``), and accuracy-under-failure becomes an ordinary scenario
axis.
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.payload import Payload


class ReplicaSet(NamedTuple):
    params: Any  # pytree, leaves (W, ...)
    opt_state: Any  # pytree, leaves (W, ...)
    steps: jax.Array  # (W,) int32 local step counters


def init_replicas(init_fn: Callable, opt_init: Callable, key, max_walks: int) -> ReplicaSet:
    """All slots start from the same initialization (footnote 4: one node
    creates the Z_0 walks — they share the initial model)."""
    params = init_fn(key)
    opt_state = opt_init(params)
    stack = lambda t: jax.tree.map(
        lambda x: jnp.broadcast_to(x, (max_walks,) + x.shape), t
    )
    return ReplicaSet(
        params=stack(params),
        opt_state=stack(opt_state),
        steps=jnp.zeros((max_walks,), jnp.int32),
    )


def fork_replica(rs: ReplicaSet, src: jax.Array, dst: jax.Array, do: jax.Array) -> ReplicaSet:
    """Copy slot src -> dst where `do` (bool scalar or (E,) events) holds."""
    src = jnp.atleast_1d(src)
    dst = jnp.atleast_1d(dst)
    do = jnp.atleast_1d(do)
    safe_dst = jnp.where(do, dst, rs.steps.shape[0])  # out-of-range -> drop

    def copy(leaf):
        return leaf.at[safe_dst].set(leaf[src], mode="drop")

    return ReplicaSet(
        params=jax.tree.map(copy, rs.params),
        opt_state=jax.tree.map(copy, rs.opt_state),
        steps=rs.steps.at[safe_dst].set(rs.steps[src], mode="drop"),
    )


def local_sgd_step(loss_fn: Callable, optimizer, params, opt_state, batch):
    """One node-local update: plain SGD/Adam on the node's mini-batch."""
    (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
    new_params, new_opt = optimizer.update(grads, opt_state, params)
    return new_params, new_opt, loss, metrics


def replica_train_step(loss_fn: Callable, optimizer):
    """vmapped per-walk local step over the slot axis.

    Returns f(rs, batches, active) -> (new rs, (W,) losses); inactive
    slots pass through unchanged.
    """

    def one(params, opt_state, batch, active):
        new_p, new_o, loss, _ = local_sgd_step(loss_fn, optimizer, params, opt_state, batch)
        sel = lambda a, b: jax.tree.map(
            lambda x, y: jnp.where(
                jnp.reshape(active, (1,) * x.ndim), x, y
            ),
            a,
            b,
        )
        return sel(new_p, params), sel(new_o, opt_state), jnp.where(active, loss, 0.0)

    vone = jax.vmap(one, in_axes=(0, 0, 0, 0))

    def step(rs: ReplicaSet, batches, active):
        new_params, new_opt, losses = vone(rs.params, rs.opt_state, batches, active)
        return (
            ReplicaSet(
                params=new_params,
                opt_state=new_opt,
                steps=rs.steps + active.astype(jnp.int32),
            ),
            losses,
        )

    return step


class RwSgdOutputs(NamedTuple):
    """Per-round learning telemetry stacked over the trajectory."""

    loss: jax.Array  # (W,) per-slot local loss (0 where no step ran)
    mean_loss: jax.Array  # scalar mean over slots that trained this round
    trained: jax.Array  # scalar int32: slots that took a local step


class RwSgdPayload(Payload):
    """The paper's workload as a pluggable payload: per-walk model
    replicas + optimizer state, advanced by batched local SGD.

    carry = :class:`ReplicaSet` (leaves with a leading ``max_walks``
    slot axis). Per round:

      * ``on_fork`` duplicates the parent's (params, opt moments, step
        counter) into the freshly allocated slot via ``fork_replica`` —
        DECAFORK's "identical copy", and the overwrite that recycles any
        stale state left by a terminated predecessor in that slot;
      * ``on_visit`` samples each live walk's mini-batch from the data
        shard of the node it just hopped to (``data.synthetic``'s
        node-keyed Markov task) and applies the vmapped local step;
        ``train_every`` > 1 thins updates to every k-th round (mask-based,
        same compiled program);
      * ``on_terminate`` is the default no-op: a dead slot's replica is
        simply never trained again and is overwritten on re-fork.

    The object is static under jit — model/optimizer/task/capacity are
    structure, the ReplicaSet is the traced state. Reuse one instance
    across runs to reuse the compiled program.
    """

    def __init__(
        self,
        model,
        optimizer,
        task,
        max_walks: int,
        local_batch: int = 2,
        seq_len: int = 32,
        train_every: int = 1,
    ):
        self.model = model
        self.optimizer = optimizer
        self.task = task
        self.max_walks = int(max_walks)
        self.local_batch = int(local_batch)
        self.seq_len = int(seq_len)
        self.train_every = int(train_every)
        self._train = replica_train_step(model.loss, optimizer)
        self._signature_cache = False  # lazily computed (task content hash)

    def signature(self):
        """Stable static-config tuple (see ``Payload.signature``): model
        config dataclass, optimizer hyperparameter signature, a content
        hash of the task's transition logits, and the capacity knobs.
        Returns None — identity semantics, no cross-process store keys —
        when the optimizer or task cannot be fingerprinted.
        """
        if self._signature_cache is not False:
            return self._signature_cache
        opt_sig = getattr(self.optimizer, "signature", None)
        model_cfg = getattr(self.model, "cfg", None)
        task_logits = getattr(self.task, "logits", None)
        if opt_sig is None or model_cfg is None or task_logits is None:
            sig = None
        else:
            import hashlib

            import numpy as np

            digest = hashlib.sha256(
                np.ascontiguousarray(np.asarray(task_logits, np.float32))
                .tobytes()
            ).hexdigest()
            sig = (
                model_cfg,
                opt_sig,
                ("task", digest),
                self.max_walks,
                self.local_batch,
                self.seq_len,
                self.train_every,
            )
        self._signature_cache = sig
        return sig

    def output_fields(self):
        return RwSgdOutputs._fields

    def validate(self, pcfg) -> None:
        if pcfg.max_walks != self.max_walks:
            raise ValueError(
                f"payload capacity max_walks={self.max_walks} does not match "
                f"ProtocolConfig.max_walks={pcfg.max_walks}"
            )

    def init(self, key: jax.Array) -> ReplicaSet:
        return init_replicas(
            self.model.init, self.optimizer.init, key, self.max_walks
        )

    def on_fork(self, rs: ReplicaSet, fork_parent: jax.Array) -> ReplicaSet:
        slots = jnp.arange(fork_parent.shape[0], dtype=jnp.int32)
        return fork_replica(
            rs, jnp.maximum(fork_parent, 0), slots, fork_parent >= 0
        )

    def on_visit(self, rs: ReplicaSet, walks, t, key):
        from repro.data.synthetic import sample_batch

        with jax.named_scope("payload.batch"):
            batches = jax.vmap(
                lambda nid: sample_batch(
                    self.task, key, self.local_batch, self.seq_len, nid
                )
            )(walks.pos)
        do = walks.active & (t % self.train_every == 0)
        with jax.named_scope("payload.step"):
            rs, losses = self._train(rs, batches, do)
        n_trained = jnp.sum(do)
        mean = jnp.sum(losses) / jnp.maximum(n_trained, 1)
        return rs, RwSgdOutputs(
            loss=losses, mean_loss=mean, trained=n_trained.astype(jnp.int32)
        )
