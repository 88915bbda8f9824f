"""Pluggable walk-payload API: the computational task the walks execute.

The paper's random walks are not an end in themselves — they *carry a
workload* (decentralized RW-SGD learning, Section I). This module defines
the seam between the self-regulation control plane (``core.simulator``)
and that workload: a :class:`Payload` owns an arbitrary pytree *carry*
that is threaded through the simulator's ``lax.scan`` alongside
``SimState``, with three hooks called once per synchronous round, in
order (mirroring the protocol's own terminate-then-fork slot lifecycle —
a slot freed this round is immediately reallocatable, so a terminated
*and* re-forked slot must be cleared before the fresh copy lands):

  ``on_terminate(carry, terminated)``
      Slots deliberately terminated this round (DECAFORK+). The default
      keeps their state in place — a later re-fork overwrites the slot
      wholesale (see ``optim.rw_sgd.fork_replica``), so clearing is only
      needed for payloads whose freed-slot state must not linger.
  ``on_fork(carry, fork_parent)``
      Walk ``fork_parent[s]`` (>= 0) was duplicated into slot ``s`` this
      round; copy slot state parent -> child (DECAFORK's "identical
      copy"). ``fork_parent`` is the per-slot parent map emitted by
      ``walkers.execute_forks`` or, for MISSINGPERSON's event grid,
      ``walkers.execute_grid_forks`` (slot allocation itself happens
      there); payloads only mirror it.
  ``on_visit(carry, walks, t, key)``
      The per-round local step: ``walks.pos[s]`` is the node slot ``s``
      sits on *after* this round's hop, ``walks.active[s]`` whether the
      slot is a live walk. Returns ``(carry, outputs)``; the per-round
      ``outputs`` pytree is stacked over time by the scan (this is the
      ``payload_outputs`` every ``run_*`` entry point returns).

``init(key) -> carry`` builds the initial carry; it runs *inside* the
compiled program, so under ``run_ensemble``/``run_sweep`` every
(scenario, seed) trajectory gets its own independently-keyed payload
state, exactly like the walk system itself.

Contract with the control plane: payload keys are folded from dedicated
stream tags (``PAYLOAD_INIT_TAG``, ``PAYLOAD_STREAM``) that the simulator
never uses, so attaching any payload — or none — leaves every simulator
random stream, and therefore every ``StepOutputs`` trajectory, bitwise
unchanged. ``payload=None`` skips the hooks entirely at trace time and is
the exact pre-payload program.

Payload objects are *static* under ``jax.jit``. By default they hash by
identity — construct one instance and reuse it across calls, or every
fresh instance recompiles. A payload that implements
:meth:`Payload.signature` (a stable tuple of its static configuration)
upgrades to *structural* identity: two instances with equal signatures
compare equal, share one compile-cache slot and one compiled program
(``repro.api.plan``), and gain a stable cross-process key for the
disk-backed result store (``repro.api.store``). Anything traced belongs
in the carry; anything structural (model definition, optimizer,
capacity) belongs on the object AND in the signature — a signature that
omits a knob which changes the computation will silently share compiled
programs between payloads that should differ.
"""
from __future__ import annotations

from typing import Any, Tuple

import jax

# dedicated PRNG stream tags; the simulator uses fold_in_time tags 0..5
PAYLOAD_INIT_TAG = 0x70AD  # folds the run key into the payload init key
PAYLOAD_STREAM = 6  # per-round on_visit key stream


class Payload:
    """Base payload: empty carry, every hook a no-op.

    Subclass and override what you need; the base class is itself a valid
    payload (useful for asserting the control plane is payload-invariant).
    See ``optim.rw_sgd.RwSgdPayload`` for the flagship implementation and
    this module's docstring for hook semantics and ordering.
    """

    def validate(self, pcfg) -> None:
        """Static compatibility check against the ProtocolConfig; called
        once per ``run_*`` entry point, outside the trace. Raise on
        mismatch (e.g. slot-capacity disagreement)."""

    def signature(self) -> Tuple | None:
        """Stable static-config tuple identifying this payload's program.

        Return a hashable tuple of everything structural — model config,
        optimizer hyperparameters, task identity, capacities — built only
        from primitives/tuples/dataclasses so it serializes stably across
        processes. Two payloads with equal signatures are treated as THE
        SAME program: they share a compile-cache slot, a compiled XLA
        program, and a result-store key. The default ``None`` keeps
        identity semantics (no structural sharing; disk-backed result
        persistence unavailable for runs carrying this payload).
        """
        return None

    def _signature_key(self) -> Tuple | None:
        """Type-qualified stable identity, or None for identity hashing."""
        sig = self.signature()
        if sig is None:
            return None
        return (type(self).__module__, type(self).__qualname__, sig)

    # structural eq/hash when a signature is declared; identity otherwise
    def __eq__(self, other):
        key = self._signature_key()
        if key is None or not isinstance(other, Payload):
            return self is other
        return key == other._signature_key()

    def __hash__(self):
        key = self._signature_key()
        return object.__hash__(self) if key is None else hash(key)

    def output_fields(self) -> Tuple[str, ...]:
        """Names of the per-round output fields this payload emits (the
        ``_fields`` of the pytree ``on_visit`` returns). Used by the
        ``outputs=`` payload-output thinning (``core.outputs``); return
        ``()`` (the default) when the payload emits no addressable
        fields — thinning is then unavailable and the full output pytree
        is recorded."""
        return ()

    def init(self, key: jax.Array) -> Any:
        """Build the initial carry pytree (traced; per-trajectory key)."""
        return ()

    def on_fork(self, carry: Any, fork_parent: jax.Array) -> Any:
        """Mirror this round's slot duplications: ``fork_parent[s]`` is the
        parent slot copied into ``s``, or -1 where no fork landed."""
        return carry

    def on_visit(
        self, carry: Any, walks, t: jax.Array, key: jax.Array
    ) -> Tuple[Any, Any]:
        """Per-round local step at the visited nodes; returns
        ``(new_carry, outputs)`` — outputs are stacked over rounds."""
        return carry, ()

    def on_terminate(self, carry: Any, terminated: jax.Array) -> Any:
        """React to deliberate terminations (boolean per-slot mask)."""
        return carry


def payload_init_key(key: jax.Array) -> jax.Array:
    """The carry-init key derived from a trajectory's run key."""
    return jax.random.fold_in(key, PAYLOAD_INIT_TAG)
