"""Walk-slot state machine: movement, forking, termination.

Fixed-shape formulation of a dynamic population: the system owns
``max_walks`` slots; a slot is a walk iff ``active[slot]``. Forking
allocates a free slot (events beyond capacity are dropped — a documented
truncation of the paper's unbounded walk population); termination frees
the slot. Allocation follows one order rule: the r-th fork event (in
event order; row-major for the MISSINGPERSON grid) takes the r-th free
slot in slot order. ``execute_forks`` applies it event-side (each event
finds its slot); ``execute_grid_forks`` slot-side (each free slot finds
the event of its rank), so no op spans the grid's W*C events.
``track[slot]`` names the column of the per-node ``last_seen`` table the
walk writes to: for DECAFORK each slot owns its own column
(fresh identity per fork, cleared on slot reuse); for MISSINGPERSON the
track is the *initial id* in [Z_0] being replaced, so replacements share
the identity of the walk they replace — exactly the paper's semantics.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.estimator import NEVER


class WalkState(NamedTuple):
    pos: jax.Array  # (W,) int32 current node
    active: jax.Array  # (W,) bool
    track: jax.Array  # (W,) int32 last_seen column owned by this walk
    # ---- zoo walk-variant memory (None unless the variant needs it; a
    # None field is an empty pytree subtree, so the default program and
    # its scan carry are structurally unchanged) -------------------------
    prev: jax.Array | None = None  # (W,) int32 previous node ('biased')
    bloom: jax.Array | None = None  # (W, bloom_bits) bool history ('bloom')


def init_walks(z0: int, max_walks: int, n_nodes: int, key: jax.Array) -> WalkState:
    """Start Z_0 walks at uniformly random nodes (footnote 4 variant)."""
    pos0 = jax.random.randint(key, (max_walks,), 0, n_nodes, dtype=jnp.int32)
    slots = jnp.arange(max_walks, dtype=jnp.int32)
    return WalkState(pos=pos0, active=slots < z0, track=slots)


def select_available_edge(row_mask: jax.Array, u: jax.Array, count_dtype):
    """Rank-select one available incident-edge slot per row, branch-free.

    ``row_mask`` is (W, D) availability over each walk's incident-edge
    slots, ``u`` the (W,) uniforms. Returns ``(adeg, sel)``: the count of
    available edges per row (``count_dtype``, == degree when the mask is
    full) and the selected slot index — the ``idx``-th available slot
    with ``idx = min(floor(u * adeg), adeg - 1)``. When every mask is
    full the available slots are exactly ``[0, degree)`` in order, so
    rank == slot index and the selection is bitwise the unmasked
    ``min(floor(u * degree), degree - 1)``. ``sel`` is garbage where
    ``adeg == 0`` (callers hold position there). Shared by the
    single-host hop (``move_walks``) and the shard_map'd distributed
    step, which must sample identically to stay in parity.
    """
    adeg = jnp.sum(row_mask, axis=1, dtype=count_dtype)
    idx = jnp.minimum((u * adeg).astype(jnp.int32), adeg - 1)
    # rank available slots per row; select the idx-th one
    rank = jnp.cumsum(row_mask, axis=1) - 1
    sel = jnp.argmax((rank == idx[:, None]) & row_mask, axis=1)
    return adeg, sel


def move_walks(
    ws: WalkState,
    neighbors: jax.Array,
    degrees: jax.Array,
    key: jax.Array,
    avail: jax.Array | None = None,
) -> WalkState:
    """One synchronous hop: each active walk moves to a uniform *available*
    neighbor.

    ``avail`` is the (n, max_deg) traversability mask from
    ``graphs.state.availability`` (None == everything up). Sampling is
    branch-free over masked slots (``select_available_edge``): draw
    u ~ U[0,1), scale by the count of available incident edges, and take
    the edge of that rank — bitwise the unmasked
    ``neighbors[pos, min(floor(u * degree), degree - 1)]`` when every
    mask is full. A walk whose node has no available incident edge
    (stranded on an isolated node) holds position.
    """
    W = ws.pos.shape[0]
    D = neighbors.shape[1]
    u = jax.random.uniform(key, (W,))
    if avail is None:
        row_mask = jnp.arange(D, dtype=degrees.dtype)[None, :] < degrees[ws.pos, None]
    else:
        row_mask = avail[ws.pos]  # (W, D)
    adeg, sel = select_available_edge(row_mask, u, degrees.dtype)
    nxt = neighbors[ws.pos, sel]
    can_move = ws.active & (adeg > 0)
    return ws._replace(pos=jnp.where(can_move, nxt, ws.pos))


def move_walks_rows(
    ws: WalkState,
    neighbors_rows: jax.Array,  # (W, D) = neighbors[ws.pos]
    u: jax.Array,  # (W,) pre-drawn hop uniforms
    avail_rows: jax.Array,  # (W, D) availability at each walk's node
    count_dtype,
) -> jax.Array:
    """Row-restricted hop: ``move_walks`` on pre-gathered walk rows.

    Takes the (W, D) adjacency and availability rows of the walks' own
    nodes (instead of gathering from the (n, D) tables internally) plus
    pre-drawn uniforms, and returns the new ``pos``. Bitwise-identical
    to ``move_walks`` with ``avail`` built from the same masks: the
    rank-select and the hold-position rule act row-locally, and
    ``take_along_axis`` on the gathered rows reads the very same
    entries as ``neighbors[pos, sel]``. This is the fused whole-round
    hop — everything it needs is (W, D)-shaped and VMEM-friendly.
    """
    adeg, sel = select_available_edge(avail_rows, u, count_dtype)
    nxt = jnp.take_along_axis(neighbors_rows, sel[:, None], axis=1)[:, 0]
    can_move = ws.active & (adeg > 0)
    return jnp.where(can_move, nxt, ws.pos)


def execute_terminations(ws: WalkState, term: jax.Array) -> WalkState:
    return ws._replace(active=ws.active & ~term)


def allocate_fork_slots(active: jax.Array, ev_mask: jax.Array):
    """Match fork events to free walk slots (capacity-capped, drop overflow).

    Ranks the free slots and the requested events, then pairs the r-th
    event with the r-th free slot. Returns ``(safe_slot, ev_ok, ev_slot)``:
    ``ev_ok`` marks events that got a slot, ``ev_slot`` is the slot each
    surviving event lands in (garbage where ``~ev_ok``), and ``safe_slot``
    is ``ev_slot`` with dropped events redirected to the out-of-range index
    ``W`` so callers can scatter with ``mode="drop"``. Shared by the
    single-host path (``execute_forks``) and the shard_map'd distributed
    step, which must allocate identically to stay replicated.
    """
    W = active.shape[0]
    slots = jnp.arange(W, dtype=jnp.int32)
    free = ~active
    n_free = jnp.sum(free)
    free_rank = jnp.cumsum(free) - 1  # rank of each slot among free ones
    ev_rank = jnp.cumsum(ev_mask) - 1  # rank of each event
    ev_ok = ev_mask & (ev_rank < n_free)
    rank_to_slot = (
        jnp.zeros((W,), jnp.int32)
        .at[jnp.where(free, free_rank, W)]
        .set(slots, mode="drop")
    )
    ev_slot = rank_to_slot[jnp.clip(ev_rank, 0, W - 1)]  # valid where ev_ok
    safe_slot = jnp.where(ev_ok, ev_slot, W)  # W = drop
    return safe_slot, ev_ok, ev_slot


def _exact_dot(a: jax.Array, b: jax.Array) -> jax.Array:
    """``a @ b`` in float32 for small integer counts: every product and sum
    is an integer below 2**24, so exact. On TPU it runs on the MXU, where
    a cumsum lowers to a slow windowed reduction."""
    return jnp.dot(
        a.astype(jnp.float32),
        b.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )


def _prefix_sums(x: jax.Array) -> jax.Array:
    """Inclusive int32 prefix sums of small counts along the last axis, as
    an exact product with a 0/1 upper triangle."""
    i = jnp.arange(x.shape[-1])
    return _exact_dot(x, i[:, None] <= i[None, :]).astype(jnp.int32)


def execute_grid_forks(
    ws: WalkState,
    last_seen: jax.Array,  # (n, C)
    ev: jax.Array,  # (W, C) bool event grid: (parent walk, identity)
):
    """MISSINGPERSON-shaped fork grid: event ``(k, l)`` forks a duplicate
    of walk ``k`` carrying identity ``l`` (replacing missing walk ``l``).

    Same result as ``execute_forks`` on the flattened grid (origin
    ``pos[k]``, track ``l``, parent ``k``), allocated slot-side: the free
    slot of rank r takes the r-th event in row-major order. Its parent
    row ``k`` is the number of rows whose events all rank below r; with
    j = r minus the events of rows before ``k``, its identity ``l`` is
    the number of leading columns of row ``k`` that hold at most j
    events. Every op is (W,), (W, W), (W, C) or (C, C): there is no
    scatter, sort or cumsum over the W*C events. ``last_seen`` is
    returned untouched: a replacement reuses the missing id's history.
    """
    W = ev.shape[0]
    rows = jnp.arange(W, dtype=jnp.int32)
    row_n = jnp.sum(ev, axis=1, dtype=jnp.int32)
    row_end = _prefix_sums(row_n)  # events in rows <= k
    free = ~ws.active
    rank = _prefix_sums(free) - 1  # rank among free slots
    fill = free & (rank < row_end[-1])
    k = jnp.sum(row_end[None, :] <= rank[:, None], axis=1, dtype=jnp.int32)
    parent = k[:, None] == rows[None, :]  # (W, W) one-hot, empty if k == W

    def of_parent(x):  # x[k] per slot, 0 where k == W
        sel = parent.reshape(parent.shape + (1,) * (x.ndim - 1))
        return jnp.sum(jnp.where(sel, x[None], 0), axis=1).astype(x.dtype)

    j = rank - of_parent(row_end - row_n)  # rank within the parent's row
    # events up to each column of the parent's row (row k of ``ev`` by a
    # one-hot product)
    seen_k = _prefix_sums(_exact_dot(parent, ev))
    track = jnp.sum(seen_k <= j[:, None], axis=1, dtype=jnp.int32)

    prev, bloom = ws.prev, ws.bloom
    if prev is not None:
        prev = jnp.where(fill, of_parent(prev), prev)
    if bloom is not None:
        bloom = jnp.where(fill[:, None], of_parent(bloom), bloom)
    return (
        WalkState(
            pos=jnp.where(fill, of_parent(ws.pos), ws.pos),
            active=ws.active | fill,
            track=jnp.where(fill, track, ws.track),
            prev=prev,
            bloom=bloom,
        ),
        last_seen,
        jnp.sum(fill),
        jnp.where(fill, k, -1),
    )


def execute_forks(
    ws: WalkState,
    last_seen: jax.Array,  # (n, C)
    ev_mask: jax.Array,  # (E,) bool fork events
    ev_origin: jax.Array,  # (E,) int32 node the fork leaves from
    ev_track: jax.Array | None,  # (E,) int32 identity, or None -> own slot
    t: jax.Array,
    ev_parent: jax.Array | None = None,  # (E,) parent walk slot per event
):
    """Allocate free slots to fork events (capacity-capped, drop overflow).

    Returns (new WalkState, new last_seen, n_forks_executed, fork_parent)
    where fork_parent[s] is the parent slot of a walk forked into slot s
    this call (-1 otherwise) — the hook the learning layer uses to
    duplicate the parent's model replica (DECAFORK's "identical copy").
    """
    W = ws.pos.shape[0]
    slots = jnp.arange(W, dtype=jnp.int32)
    safe_slot, ev_ok, ev_slot = allocate_fork_slots(ws.active, ev_mask)

    if ev_parent is None:
        ev_parent = jnp.arange(ev_mask.shape[0], dtype=jnp.int32)
    fork_parent = (
        jnp.full((W,), -1, jnp.int32).at[safe_slot].set(ev_parent, mode="drop")
    )
    active = ws.active.at[safe_slot].set(True, mode="drop")
    pos = ws.pos.at[safe_slot].set(ev_origin, mode="drop")
    if ev_track is None:
        # DECAFORK: fresh identity = the slot itself; clear the stale column
        track = ws.track.at[safe_slot].set(ev_slot, mode="drop")
        fresh = jnp.zeros((W,), bool).at[safe_slot].set(True, mode="drop")
        col_origin = jnp.zeros((W,), jnp.int32).at[safe_slot].set(ev_origin, mode="drop")
        last_seen = jnp.where(fresh[None, :], NEVER, last_seen)
        # the forking node has, by construction, just seen the new walk
        last_seen = last_seen.at[col_origin, slots].add(
            jnp.where(fresh, t - NEVER, 0).astype(last_seen.dtype)
        )
    else:
        # MISSINGPERSON: replacement carries the missing walk's identity
        track = ws.track.at[safe_slot].set(ev_track, mode="drop")
    # zoo variant memory forks with the slot: the child duplicates the
    # parent's previous-node column and Bloom history
    prev = ws.prev
    if prev is not None:
        prev = prev.at[safe_slot].set(prev[ev_parent], mode="drop")
    bloom = ws.bloom
    if bloom is not None:
        bloom = bloom.at[safe_slot].set(bloom[ev_parent], mode="drop")
    return (
        WalkState(pos=pos, active=active, track=track, prev=prev, bloom=bloom),
        last_seen,
        jnp.sum(ev_ok),
        fork_parent,
    )
