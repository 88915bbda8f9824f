"""Node-sharded distributed protocol step (shard_map).

The paper's system is decentralized: every node acts on *local* state only
(Rule 1). This maps naturally onto SPMD: we shard the per-node protocol
state — ``last_seen`` rows, return-time histograms — across a 1-D device
axis (or a flattened ('pod','data') pair for the multi-pod mesh), while the
O(Z) walk descriptors (positions, active flags, tracks) stay replicated.

Per round each device:
  1. computes next hops for the walks currently sitting on *its* nodes
     (it owns their neighbor lists, and the live-topology masks for its
     rows) and contributes them to a psum — the SPMD analogue of "the
     holding node forwards the token". Movement samples over *available*
     incident edges (``GraphState`` semantics: down nodes/links are
     unreachable, a stranded walk holds position, a crashed node kills
     its residents), matching the single-device ``walkers.move_walks``
     path bit-for-bit on a 1-device mesh;
  2. records return-time samples / last-seen updates for its own rows;
  3. evaluates theta-hat and the fork/terminate rule for walks choosing
     its nodes, and contributes decision masks to a psum — decisions are
     node-local, exactly Rule 1; the psum is the message exchange.

Only two collectives per round (both over the O(max_walks) walk axis), so
collective bytes are independent of graph size — the protocol scales to
arbitrarily large node counts. This is the paper technique as a
first-class distributed feature; ``launch/dryrun.py`` lowers it for the
production meshes alongside the payload train steps.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import estimator as est
from repro.core import protocol as prt
from repro.core import walkers as wlk
from repro.core.walkers import WalkState
from repro.graphs.state import availability_rows
from jax import shard_map
from repro.utils.prng import fold_in_time


class ShardedProtocolState(NamedTuple):
    """Walk state replicated; node tables sharded on their first axis."""

    t: jax.Array
    pos: jax.Array  # (W,) replicated
    active: jax.Array  # (W,) replicated
    track: jax.Array  # (W,) replicated
    last_seen: jax.Array  # (n, W) node-sharded
    hist: jax.Array  # (n, B) node-sharded
    total: jax.Array  # (n,) node-sharded
    key: jax.Array  # replicated


def make_sharded_step(
    mesh: Mesh,
    node_axes: Sequence[str],
    n_nodes: int,
    pcfg: prt.ProtocolConfig,
):
    """Build the shard_map'd protocol round for `mesh` with nodes sharded
    over `node_axes` (e.g. ('data',) or ('pod', 'data')).

    The step takes the live-topology masks as trailing arguments:
    ``node_up`` (n,) bool replicated — availability needs the liveness of
    *neighbor* nodes, which live on other shards, so the cheap O(n)-bool
    vector stays replicated rather than adding a gather collective — and
    ``edge_up`` (n, max_deg) bool node-sharded alongside ``neighbors``.
    Pass all-True masks for a static topology (bitwise the unmasked hop).
    """

    axes = tuple(node_axes)
    n_shards = 1
    for a in axes:
        n_shards *= mesh.shape[a]
    if n_nodes % n_shards:
        raise ValueError(f"n_nodes={n_nodes} must divide over {n_shards} shards")
    n_local = n_nodes // n_shards

    node_spec = P(axes)
    rep = P()
    in_specs = (
        rep,  # t
        rep,  # pos
        rep,  # active
        rep,  # track
        node_spec,  # last_seen
        node_spec,  # hist
        P(axes),  # total
        rep,  # key
        node_spec,  # neighbors
        P(axes),  # degrees
        rep,  # node_up — replicated: availability needs neighbor liveness
        node_spec,  # edge_up
    )
    out_specs = (rep, rep, rep, rep, node_spec, node_spec, P(axes), rep, rep)

    def _shard_offset():
        off = jnp.int32(0)
        for a in axes:
            off = off * mesh.shape[a] + jax.lax.axis_index(a)
        return off * n_local

    def step(
        t, pos, active, track, last_seen, hist, total, key, neighbors, degrees,
        node_up, edge_up,
    ):
        W = pos.shape[0]
        lo = _shard_offset()
        # a down node kills its resident walks (kill_resident_walks parity;
        # node_up is replicated, so this needs no collective)
        active = active & node_up[pos]
        local = active & (pos >= lo) & (pos < lo + n_local)
        lpos = jnp.clip(pos - lo, 0, n_local - 1)

        # --- 1. movement: owner shard proposes the next hop over the
        # currently *available* incident edges (the same shared
        # rank-select as walkers.move_walks — bitwise-identical sampling
        # is what keeps the two paths in parity); a stranded walk
        # proposes its own position.
        k_move = fold_in_time(key, t, 0)
        u = jax.random.uniform(k_move, (W,))
        up_local = jax.lax.dynamic_slice_in_dim(node_up, lo, n_local)
        avail = availability_rows(edge_up, up_local, node_up, neighbors, degrees)
        row_mask = avail[lpos]  # (W, D)
        adeg, sel = wlk.select_available_edge(row_mask, u, degrees.dtype)
        nxt_local = neighbors[lpos, sel]
        proposal = jnp.where(local, jnp.where(adeg > 0, nxt_local, pos), 0)
        new_pos = jax.lax.psum(proposal, axes)
        pos = jnp.where(active, new_pos, pos)

        # --- 2. observations on local rows -------------------------------
        local = active & (pos >= lo) & (pos < lo + n_local)
        lpos = jnp.clip(pos - lo, 0, n_local - 1)
        prev = last_seen[lpos, track]
        r = t - prev
        valid = local & (prev != est.NEVER) & (r >= 1)
        bins = hist.shape[1]
        b = jnp.clip(r, 1, bins) - 1
        w = valid.astype(jnp.float32)
        hist = hist.at[lpos, b].add(jnp.where(local, w, 0.0), mode="drop")
        total = total.at[lpos].add(jnp.where(local, w, 0.0), mode="drop")
        upd = jnp.where(local, t, est.NEVER)
        last_seen = last_seen.at[lpos, track].max(upd, mode="drop")

        # --- 3. node-local estimates + decisions -------------------------
        slots = jnp.arange(W, dtype=jnp.int32)
        cand = jnp.where(local, slots, W)
        best = jnp.full((n_local,), W, jnp.int32).at[lpos].min(
            jnp.where(local, cand, W), mode="drop"
        )
        chosen = local & (best[lpos] == slots)

        cum = jnp.concatenate(
            [jnp.zeros_like(hist[:, :1]), jnp.cumsum(hist, axis=1)], axis=1
        )
        ls_rows = last_seen[lpos]  # (W, C)
        elapsed = t - ls_rows
        nodes_b = jnp.broadcast_to(lpos[:, None], ls_rows.shape)
        s = est.survival_eval(cum, total, nodes_b, elapsed)
        cols = jnp.arange(ls_rows.shape[1])[None, :]
        mask = (ls_rows != est.NEVER) & (cols != track[:, None])
        theta = 0.5 + jnp.sum(jnp.where(mask, s, 0.0), axis=1)

        enabled = t >= pcfg.protocol_start
        k_dec = fold_in_time(key, t, 4)
        fork_local, term_local = prt.decafork_decisions(
            theta, chosen, k_dec, pcfg, enabled
        )
        # --- decision exchange: disjoint masks -> psum ---------------------
        fork = jax.lax.psum(fork_local.astype(jnp.int32), axes) > 0
        term = jax.lax.psum(term_local.astype(jnp.int32), axes) > 0

        # --- 4. execute (replicated, deterministic) ------------------------
        active = active & ~term
        ev_origin = pos  # forked walk starts where its parent sits
        safe_slot, ev_ok, ev_slot = wlk.allocate_fork_slots(active, fork)
        active = active.at[safe_slot].set(True, mode="drop")
        pos = pos.at[safe_slot].set(ev_origin, mode="drop")
        track = track.at[safe_slot].set(ev_slot, mode="drop")
        # clear the reused local column + mark the fork origin if local
        fresh = jnp.zeros((W,), bool).at[safe_slot].set(ev_ok, mode="drop")
        col_origin = jnp.zeros((W,), jnp.int32).at[safe_slot].set(
            jnp.clip(ev_origin - lo, 0, n_local - 1), mode="drop"
        )
        origin_is_local = jnp.zeros((W,), bool).at[safe_slot].set(
            ev_ok & (ev_origin >= lo) & (ev_origin < lo + n_local), mode="drop"
        )
        last_seen = jnp.where(fresh[None, :], est.NEVER, last_seen)
        last_seen = last_seen.at[col_origin, slots].add(
            jnp.where(origin_is_local & fresh, t - est.NEVER, 0).astype(
                last_seen.dtype
            )
        )

        z = jnp.sum(active)
        return t + 1, pos, active, track, last_seen, hist, total, key, z

    return shard_map(step, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                     check_vma=False)
