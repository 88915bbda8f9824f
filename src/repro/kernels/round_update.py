"""Fused per-round observation kernel: scatter + max-update + theta sums.

Every simulator round runs the observation pipeline at the visited nodes
(``core/simulator.py`` step 4-5):

  1. ``record_returns``   — scatter observed return times into the
                            per-node histograms ``hist (n, B)`` / ``total``;
  2. ``last_seen`` update — scatter-max the visit times into ``(n, C)``;
  3. node theta sums      — sum_c S_i(t - last_seen[i, c]) per node
                            (Eq. 1's node-side reduction).

Unfused, 3. alone either re-builds the full ``(n, B+1)`` cumulative table
every round (gather path) or re-materializes an ``(n, C, B)`` compare
intermediate from HBM (compare path), and 1.-2. are separate scatter
dispatches touching the same rows again. This module fuses all three into
ONE node-tiled Pallas pass: each grid program holds a ``(bn, ...)`` tile
of ``last_seen`` / ``hist`` / ``total`` in VMEM, applies the round's walk
events to its tile (one-hot contractions — no scatter, no gather), and
reduces the theta sums for its rows while they are still resident. The
``(bn, C, B)`` compare intermediate never leaves VMEM, and per-round HBM
traffic drops to one read + one write of the observation state.

Exactness contract: ``hist``/``total`` hold event *counts* (int16/int32
as ``record_returns`` maintains — per-bin counts are step-bounded, far
below 32767; the f32 one-hot matmul accumulates exact small integers that
cast back losslessly) and the walk weights are 0/1, so the kernel updates
bitwise what the reference scatter-adds would; the max-updates are
integer ops. The kernel is therefore *bitwise* equal to the unfused
reference sequence — ``round_update_ref`` (which literally IS that
sequence, with ``estimator.node_sums_compare`` as the sums oracle) — and
is golden-tested as such, including node counts that are not a multiple
of the tile (padded with masked "no data" rows). Both kernels are
dtype-polymorphic (outputs follow the input carry), so the benchmark
grid can still measure a float32 arm.

``round_update`` dispatches per backend (``kernels.platform``): the
Pallas kernel on TPU, the fused-at-the-jnp-level reference elsewhere.
The simulator selects this whole path with ``estimator_impl="fused"``.

``whole_round_pallas`` extends the fusion to the ENTIRE round: one
node-tiled two-phase pass performing the topology step, resident-walk
kills, the masked rank-select hop, walk-level failures (probabilistic /
burst / Byzantine / Pac-Man), the observation update above, AND the
fork/terminate decision masks — everything between two scan carries
except the walk-slot fork/terminate execution, which stays outside. All
uniforms are pre-drawn by the caller from the exact PRNG streams the
unfused sequence consumes (``core.simulator._protocol_step_fused``), so
the kernel is deterministic data flow and bitwise-testable against the
literal unfused round.

Zoo coverage (``repro.zoo``): the kernel handles the classic single
static Pac-Man only. The zoo's attack statics — ``pacman_mobile``,
extra ``pacman_nodes``, scheduled edge cuts — and every non-uniform
``walk_variant`` are gated OFF this kernel by
``core.simulator.round_impl_decision`` (the ref fused round, which
shares the jnp failure helpers, still fuses the attack statics; walk
variants always take the stage sequence). Extending the kernel to the
zoo attacks rides the real-TPU validation item in ROADMAP item 3.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import estimator as est
from repro.core import protocol as prt
from repro.core import walkers as wlk
from repro.kernels.platform import (
    best_round_update_impl,
    default_interpret,
    pad_node_axis,
)

DEFAULT_BLOCK_NODES = 8
NEVER = est.NEVER


def random_round_inputs(key, n, C, B, W, t=70, p_active=0.8):
    """A plausible mid-trajectory observation round honoring the input
    contract (integer-valued count histograms, ``r``/``valid``/``upd``
    derived exactly as the simulator derives them) — the shared fixture
    for the bitwise oracle tests, the benchmark grid and the CI smoke
    tripwire. Returns ``(last_seen, hist, total, pos, track, r, valid,
    upd, t)``, i.e. ``round_update``'s argument tuple."""
    ks = jax.random.split(key, 5)
    ls = jax.random.randint(ks[0], (n, C), -1, t, dtype=jnp.int32)
    hist = jnp.floor(jax.random.uniform(ks[1], (n, B)) * 3).astype(jnp.int16)
    total = hist.sum(1, dtype=jnp.int32)
    pos = jax.random.randint(ks[2], (W,), 0, n, dtype=jnp.int32)
    track = jax.random.randint(ks[3], (W,), 0, C, dtype=jnp.int32)
    active = jax.random.uniform(ks[4], (W,)) < p_active
    t = jnp.int32(t)
    prev = ls[pos, track]
    r = t - prev
    valid = active & (prev != NEVER) & (r >= 1)
    upd = jnp.where(active, t, NEVER)
    return ls, hist, total, pos, track, r, valid, upd, t


def round_update_ref(last_seen, hist, total, pos, track, r, valid, upd, t):
    """The unfused reference sequence (and the Pallas kernel's bitwise
    oracle): ``record_returns`` -> ``last_seen`` scatter-max ->
    ``node_sums_compare``. Returns ``(last_seen, hist, total, sums)``."""
    rts = est.record_returns(est.ReturnTimeState(hist, total), pos, r, valid)
    ls = last_seen.at[pos, track].max(upd, mode="drop")
    sums = est.node_sums_compare(ls, rts.hist, rts.total, t)
    return ls, rts.hist, rts.total, sums


def _scatter_max_rows(hit, track, upd, C):
    """(bn, C) tile of the scatter-max ``[pos[j], track[j]] <- upd[j]``:
    row i, column c takes the max ``upd[j]`` over the walks j with
    ``hit[i, j]`` and ``track[j] == c`` (NEVER where none). The (bn, W, C)
    select runs on an int32 mask: Mosaic cannot lay out the 3-D
    broadcast of a bool."""
    W = hit.shape[1]
    col_iota = jax.lax.broadcasted_iota(jnp.int32, (W, C), 1)
    m = jnp.where(
        col_iota == jnp.reshape(track, (W, 1)), jnp.reshape(upd, (W, 1)), NEVER
    )  # (W, C)
    hit3 = hit.astype(jnp.int32)[:, :, None]
    return jnp.max(jnp.where(hit3 > 0, m[None, :, :], NEVER), axis=1)


def _round_kernel(
    t_ref, pos_ref, track_ref, rbin_ref, w_ref, upd_ref,
    ls_ref, hist_ref, tot_ref,
    ls_out, hist_out, tot_out, sums_out,
):
    t = t_ref[0, 0]
    pos = pos_ref[0, :]  # (W,) node visited by each walk slot
    track = track_ref[0, :]  # (W,) column each walk writes
    rbin = rbin_ref[0, :]  # (W,) histogram bin of the observed return
    w = w_ref[0, :]  # (W,) 0/1 observation weight
    upd = upd_ref[0, :]  # (W,) last-seen update value (NEVER if inactive)
    ls = ls_ref[...]  # (bn, C) int32
    hist = hist_ref[...]  # (bn, B) int16 counts (or f32 on the bench arm)
    tot = tot_ref[...]  # (bn, 1) int32 counts (or f32 on the bench arm)
    bn, C = ls.shape
    B = hist.shape[1]
    W = pos.shape[0]

    base = pl.program_id(0) * bn
    rows = jax.lax.broadcasted_iota(jnp.int32, (bn, W), 0) + base
    hit = rows == pos[None, :]  # (bn, W): walk j visits row i of this tile

    # 1. return-time scatter as a one-hot contraction: the f32 matmul
    #    accumulates exact small integers (counts are step-bounded, far
    #    below 2**24), so the cast back to the carry dtype is lossless
    #    and the result is bitwise the reference scatter-adds
    bin_iota = jax.lax.broadcasted_iota(jnp.int32, (W, B), 1)
    ev = jnp.where(bin_iota == rbin[:, None], w[:, None], 0.0)  # (W, B)
    hist = hist + jnp.dot(hit.astype(jnp.float32), ev).astype(hist.dtype)
    tot = tot + jnp.sum(
        jnp.where(hit, w[None, :], 0.0), axis=1, keepdims=True
    ).astype(tot.dtype)

    # 2. last-seen scatter-max at (pos[j], track[j]) <- upd[j]
    ls = jnp.maximum(ls, _scatter_max_rows(hit, track, upd, C))

    # 3. theta sums on the updated tile: the shared compare-accumulate
    #    core (estimator.survival_node_sums_rows), VMEM-resident
    ls_out[...] = ls
    hist_out[...] = hist
    tot_out[...] = tot
    sums_out[...] = est.survival_node_sums_rows(ls, hist, tot[:, 0], t)[:, None]


@functools.partial(jax.jit, static_argnames=("block_nodes", "interpret"))
def round_update_pallas(
    last_seen: jax.Array,  # (n, C) int32
    hist: jax.Array,  # (n, B) int16 counts (f32 bench arm also supported)
    total: jax.Array,  # (n,) int32 counts (f32 bench arm also supported)
    pos: jax.Array,  # (W,) int32
    track: jax.Array,  # (W,) int32
    r: jax.Array,  # (W,) int32 observed return times (t - prev)
    valid: jax.Array,  # (W,) bool
    upd: jax.Array,  # (W,) int32 last-seen update (NEVER if inactive)
    t: jax.Array,  # scalar int32
    *,
    block_nodes: int = DEFAULT_BLOCK_NODES,
    interpret: bool | None = None,
):
    """One fused observation round over node tiles; see module docstring.

    Returns ``(last_seen, hist, total, sums)`` with the round's walk
    events applied and ``sums[i] = sum_c S_i(t - last_seen[i, c])``.
    ``n`` need not divide the tile: the node axis is padded with masked
    "no data" rows (sliced off again) that no walk can hit. NB the
    pad+slice happens per call, so a non-tile-multiple ``n`` inside a
    scanned trajectory pays one extra copy of the observation state per
    round — pick ``n`` (or ``block_nodes``) tile-aligned on the hot
    path, or carry pre-padded state (ROADMAP follow-up).
    """
    n, C = last_seen.shape
    B = hist.shape[1]
    W = pos.shape[0]
    if interpret is None:
        interpret = default_interpret()
    bn = min(block_nodes, n)
    last_seen, hist, total, pad = pad_node_axis(bn, last_seen, hist, total)
    npad = n + pad
    rbin = jnp.clip(r, 1, B) - 1  # record_returns' bin rule
    w = valid.astype(jnp.float32)
    t_arr = jnp.asarray(t, jnp.int32).reshape(1, 1)
    walk_spec = pl.BlockSpec((1, W), lambda i: (0, 0))  # broadcast to tiles
    ls_o, hist_o, tot_o, sums_o = pl.pallas_call(
        _round_kernel,
        grid=(npad // bn,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0)),  # t (broadcast)
            walk_spec,  # pos
            walk_spec,  # track
            walk_spec,  # rbin
            walk_spec,  # w
            walk_spec,  # upd
            pl.BlockSpec((bn, C), lambda i: (i, 0)),  # last_seen tile
            pl.BlockSpec((bn, B), lambda i: (i, 0)),  # hist tile
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),  # total tile
        ],
        out_specs=[
            pl.BlockSpec((bn, C), lambda i: (i, 0)),
            pl.BlockSpec((bn, B), lambda i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((npad, C), last_seen.dtype),
            jax.ShapeDtypeStruct((npad, B), hist.dtype),
            jax.ShapeDtypeStruct((npad, 1), total.dtype),
            jax.ShapeDtypeStruct((npad, 1), jnp.float32),
        ],
        interpret=interpret,
    )(
        t_arr,
        pos[None, :],
        track[None, :],
        rbin[None, :],
        w[None, :],
        upd[None, :],
        last_seen,
        hist,
        total[:, None],
    )
    return ls_o[:n], hist_o[:n], tot_o[:n, 0], sums_o[:n, 0]


def round_update(
    last_seen, hist, total, pos, track, r, valid, upd, t,
    *, impl: str | None = None,
):
    """Backend-dispatched fused round: ``impl=None`` resolves through
    ``kernels.platform.best_round_update_impl`` ('pallas' on TPU, 'ref'
    on CPU/GPU). Both implementations are bitwise-interchangeable."""
    if impl is None:
        impl = best_round_update_impl()
    if impl == "pallas":
        return round_update_pallas(
            last_seen, hist, total, pos, track, r, valid, upd, t
        )
    if impl == "ref":
        return round_update_ref(
            last_seen, hist, total, pos, track, r, valid, upd, t
        )
    raise ValueError(f"unknown round impl {impl!r}; use 'pallas' or 'ref'")


# ---------------------------------------------------------------------------
# Whole-round kernel: topology + hop + failures + observations + decisions
# ---------------------------------------------------------------------------


def _take(col, idx):
    """``col[idx]`` for an (N, 1) int32 column and (1, W) indices, as a
    one-hot compare-and-reduce (Mosaic lowers no vector gather). An index
    outside [0, N) reads 0. Values must be >= 0 (masks, counts)."""
    oh = jax.lax.broadcasted_iota(jnp.int32, (col.shape[0], idx.shape[1]), 0)
    return jnp.max(jnp.where(oh == idx, col, 0), axis=0, keepdims=True)


def _select(c, a, b):
    """``jnp.where(c, a, b)`` for bool ``a``/``b``: Mosaic cannot lower a
    select between bool vectors."""
    return (c & a) | (~c & b)


def _whole_round_kernel(
    decafork_plus,
    # broadcast scalars
    params_f_ref,  # (1, 8) f32: p_fail, p_nfail, p_lfail, p_nrec, p_lrec,
    #                            eps, eps2, fork_prob (start-gates folded in)
    params_i_ref,  # (1, 4) i32: t, byz_kill_node, pacman_node, enabled
    # walk-level inputs (broadcast to every tile)
    pos_ref, track_ref, act_ref,  # (1, W) i32 / i32 / i32 0-1 mask
    u_move_ref, u_pfail_ref, u_fork_ref, u_term_ref,  # (1, W) f32
    deg_ref,  # (1, W) i32 degrees at the walks' pre-hop nodes
    nbrw_ref, eupw_ref, efw_ref, erw_ref,  # (D, W) walk-row adjacency/masks
    uburst_ref,  # (K', W) f32 per-burst score uniforms
    bsz_ref,  # (1, K') i32 effective burst sizes (0 when not firing)
    # node-level inputs
    nodeup_ref, unfail_ref, unrec_ref, sched_ref,  # (1, N) full node axis
    eup_ref, ef_ref, er_ref,  # (bn, D) edge tiles: mask + symmetrized u's
    ls_ref, hist_ref, tot_ref,  # (bn, C) i32 / (bn, B) i16 / (bn, 1) i32
    # outputs
    ls_out, hist_out, tot_out,  # updated observation tiles
    eup_out,  # (bn, D) updated edge tile (i32 0-1)
    nodeup_out,  # (1, N) updated node mask (i32 0-1, constant block)
    pos_out, act_out,  # (1, W) post-hop / post-failure walk state
    theta_out,  # (1, W) f32 theta-hat accumulator -> final theta
    chosen_out, fork_out, term_out,  # (1, W) i32 0-1 decision masks
):
    """Two-phase whole-round pass; grid = (2, num_tiles), phase-major.

    Phase 0 advances the topology per tile and, in its first step, runs
    the walk epilogue (resident kills, masked rank-select hop, walk-level
    failures) on the full walk vectors, publishing ``pos_out``/``act_out``
    for phase 1 to read. Phase 1 applies the observation update to each
    tile (the PR-4 fused pipeline) and accumulates per-walk theta sums
    into ``theta_out``; its last step computes the fork/terminate masks.
    Output blocks with constant index maps persist across grid steps
    (the standard Pallas accumulation idiom), which is what carries the
    walk state and theta accumulator between phases. Tile-mapped outputs
    are written in BOTH phases (topology recomputed, observation tiles
    passed through in phase 0) so no revisited block holds stale data.

    Walk vectors stay ``(1, W)`` rows; a value indexed by walk is read
    with a one-hot compare-and-reduce (``_take``) against an ``(N, 1)``
    or ``(C, 1)`` column, and walk-pair comparisons pair a row with its
    transpose. Scalars stay ``(1, 1)`` blocks that broadcast. Masks cross
    the kernel boundary as int32 0/1: Mosaic cannot load narrow bool
    tiles such as the ``(bn, D)`` edge block.
    """
    ph = pl.program_id(0)
    i = pl.program_id(1)
    pf = params_f_ref[...]
    pint = params_i_ref[...]
    t = pint[:, 0:1]
    byz_node = pint[:, 1:2]
    pac_node = pint[:, 2:3]
    enabled = pint[:, 3:4] > 0
    p_fail, p_nfail, p_lfail = pf[:, 0:1], pf[:, 1:2], pf[:, 2:3]
    p_nrec, p_lrec, eps = pf[:, 3:4], pf[:, 4:5], pf[:, 5:6]
    eps2, p_fork = pf[:, 6:7], pf[:, 7:8]

    # -- edge-tile topology update, recomputed in both phases so every
    #    mapped output block is written on every grid step
    eup = eup_ref[...] != 0
    fail = ef_ref[...] < p_lfail
    rec = er_ref[...] < p_lrec
    eup_out[...] = _select(eup, ~fail, rec).astype(jnp.int32)

    @pl.when(ph == 0)
    def _pass_through_obs():
        ls_out[...] = ls_ref[...]
        hist_out[...] = hist_ref[...]
        tot_out[...] = tot_ref[...]

    @pl.when((ph == 0) & (i == 0))
    def _walk_epilogue():
        # the full updated node mask (the epilogue needs it for kills
        # and for BOTH hop endpoints)
        node_up = nodeup_ref[...] != 0
        crash = unfail_ref[...] < p_nfail
        recov = unrec_ref[...] < p_nrec
        sched = sched_ref[...] != 0
        node_new = _select(node_up, ~(crash | sched), recov & ~sched)
        node_new = node_new.astype(jnp.int32)
        nodeup_out[...] = node_new
        node_col = node_new.T  # (N, 1)
        pos = pos_ref[...]
        # resident kills at the pre-hop positions
        up_at_pos = _take(node_col, pos) > 0
        active = (act_ref[...] != 0) & up_at_pos
        # masked rank-select hop over the walks' own adjacency rows:
        # walkers.select_available_edge, one edge slot d at a time
        nbr = nbrw_ref[...]
        eup_w = _select(
            eupw_ref[...] != 0, ~(efw_ref[...] < p_lfail), erw_ref[...] < p_lrec
        )
        deg = deg_ref[...]
        D = nbr.shape[0]
        avail = [
            (d < deg)
            & eup_w[d:d + 1, :]
            & up_at_pos
            & (_take(node_col, nbr[d:d + 1, :]) > 0)
            for d in range(D)
        ]
        adeg = sum(a.astype(jnp.int32) for a in avail)
        idx = jnp.minimum((u_move_ref[...] * adeg).astype(jnp.int32), adeg - 1)
        rank = jnp.full_like(adeg, -1)
        nxt = pos
        for d in range(D):
            rank = rank + avail[d].astype(jnp.int32)
            nxt = jnp.where(avail[d] & (rank == idx), nbr[d:d + 1, :], nxt)
        pos = jnp.where(active & (adeg > 0), nxt, pos)
        # walk-level threat models: probabilistic, bursts, Byz, Pac-Man
        active = active & ~(u_pfail_ref[...] < p_fail)
        for b in range(uburst_ref.shape[0]):
            score = jnp.where(active, uburst_ref[b:b + 1, :], jnp.inf)
            # rank[j] = #{k : score[j] > score[k]}
            rank = jnp.sum(
                (score.T < score).astype(jnp.int32), axis=0, keepdims=True
            )
            active = active & ~(rank < bsz_ref[:, b:b + 1])
        active = active & ~(pos == byz_node)  # -1 sentinels never match
        active = active & ~(pos == pac_node)
        pos_out[...] = pos
        act_out[...] = active.astype(jnp.int32)

    @pl.when(ph == 1)
    def _observe_and_decide():
        pos = pos_out[...]
        active = act_out[...] != 0
        track = track_ref[...]
        ls = ls_ref[...]
        hist = hist_ref[...]
        tot = tot_ref[...]
        bn, C = ls.shape
        B = hist.shape[1]
        W = pos.shape[1]
        base = i * bn
        rows = jax.lax.broadcasted_iota(jnp.int32, (bn, W), 0) + base
        hit = rows == pos
        # prev = last_seen[pos, track]: a walk's row lives in exactly one
        # tile, so the masked max over this tile IS the gather for the
        # walks that land here (others see NEVER -> no contribution)
        c_iota = jax.lax.broadcasted_iota(jnp.int32, (bn, C, W), 1)
        ls_track = jnp.max(
            jnp.where(c_iota == track[None, :, :], ls[:, :, None], NEVER),
            axis=1,
        )  # (bn, W) = ls[:, track]
        prev = jnp.max(
            jnp.where(hit, ls_track, NEVER), axis=0, keepdims=True
        )
        r = t - prev
        valid = active & (prev != NEVER) & (r >= 1)
        rbin = jnp.clip(r, 1, B) - 1
        w8 = valid.astype(jnp.float32)
        bin_iota = jax.lax.broadcasted_iota(jnp.int32, (W, B), 1)
        ev = jnp.where(bin_iota == rbin.T, w8.T, 0.0)  # (W, B)
        hist = hist + jnp.dot(hit.astype(jnp.float32), ev).astype(hist.dtype)
        tot = tot + jnp.sum(
            jnp.where(hit, w8, 0.0), axis=1, keepdims=True
        ).astype(tot.dtype)
        upd = jnp.where(active, t, NEVER)
        ls = jnp.maximum(ls, _scatter_max_rows(hit, track, upd, C))
        ls_out[...] = ls
        hist_out[...] = hist
        tot_out[...] = tot
        # per-walk theta contribution from this tile's node sums
        sums = est.survival_node_sums_rows(ls, hist, tot[:, 0], t)
        contrib = jnp.sum(
            jnp.where(hit, jnp.reshape(sums, (bn, 1)), 0.0),
            axis=0,
            keepdims=True,
        )
        acc = jnp.where(i == 0, contrib, theta_out[...] + contrib)
        theta_out[...] = acc

        @pl.when(i == pl.num_programs(1) - 1)
        def _decide():
            theta = acc - 0.5  # theta_hat_from_node_sums
            theta_out[...] = theta
            # protocol.choose_walks_pairwise: each walk takes the min
            # candidate slot over the walks sharing its node
            slots = jax.lax.broadcasted_iota(jnp.int32, (1, W), 1)
            cand = jnp.where(active, slots, W)
            best = jnp.min(
                jnp.where(pos.T == pos, cand.T, W), axis=0, keepdims=True
            )
            chosen = active & (best == slots)
            fork = chosen & (theta < eps) & (u_fork_ref[...] < p_fork) & enabled
            if decafork_plus:
                term = (
                    chosen
                    & (theta > eps2)
                    & (u_term_ref[...] < p_fork)
                    & enabled
                )
                term = term & ~fork
            else:
                term = jnp.zeros_like(fork)
            chosen_out[...] = chosen.astype(jnp.int32)
            fork_out[...] = fork.astype(jnp.int32)
            term_out[...] = term.astype(jnp.int32)


@functools.partial(
    jax.jit,
    static_argnames=("decafork_plus", "block_nodes", "interpret"),
)
def whole_round_pallas(
    last_seen: jax.Array,  # (n, C) int32
    hist: jax.Array,  # (n, B) int16 counts
    total: jax.Array,  # (n,) int32 counts
    node_up: jax.Array,  # (n,) bool live-node mask (pre-round)
    edge_up: jax.Array,  # (n, D) bool live-edge mask (pre-round)
    pos: jax.Array,  # (W,) int32 pre-hop positions
    track: jax.Array,  # (W,) int32
    active: jax.Array,  # (W,) bool pre-round liveness
    neighbors_rows: jax.Array,  # (W, D) = neighbors[pos]
    degrees_rows: jax.Array,  # (W,) = degrees[pos]
    edge_up_rows: jax.Array,  # (W, D) = edge_up[pos]
    e_fail_rows: jax.Array,  # (W, D) symmetrized link-fail uniforms at pos
    e_rec_rows: jax.Array,  # (W, D) symmetrized link-recovery uniforms
    u_move: jax.Array,  # (W,) hop uniforms
    u_pfail: jax.Array,  # (W,) probabilistic-failure uniforms
    u_fork: jax.Array,  # (W,) fork-decision uniforms
    u_term: jax.Array,  # (W,) terminate-decision uniforms
    u_burst: jax.Array,  # (K', W) per-burst score uniforms
    burst_sizes_eff: jax.Array,  # (K',) i32, 0 where the burst is not firing
    u_nfail: jax.Array,  # (n,) node crash uniforms
    u_nrec: jax.Array,  # (n,) node recovery uniforms
    sched_down: jax.Array,  # (n,) bool scheduled-crash mask for this step
    e_fail: jax.Array,  # (n, D) symmetrized link-fail uniforms, full table
    e_rec: jax.Array,  # (n, D) symmetrized link-recovery uniforms
    params_f: jax.Array,  # (1, 8) f32 — see _whole_round_kernel
    params_i: jax.Array,  # (1, 4) i32 — see _whole_round_kernel
    *,
    decafork_plus: bool = False,
    block_nodes: int = DEFAULT_BLOCK_NODES,
    interpret: bool | None = None,
):
    """One whole simulator round as a single node-tiled Pallas pass.

    Every random draw is made by the caller (from the exact PRNG streams
    the unfused sequence consumes) and enters as data, so the kernel is
    deterministic and bitwise-testable against the literal unfused round.
    Start-gates are folded into effective rates/sentinels by the caller:
    a rate of -1 never fires (uniforms live in [0, 1)), a node id of -1
    never matches. Returns

      ``(last_seen, hist, total, node_up, edge_up, pos, active, theta,
      chosen, fork, term)``

    — the updated observation state, the stepped topology masks, the
    post-hop post-failure walk state, per-walk theta-hat, and the
    decision masks for ``execute_forks`` / ``execute_terminations``
    (which stay outside: they are walk-sized and shared with every other
    path). ``n`` need not divide the tile; the node axis is padded with
    masked rows no walk can reach and sliced off the outputs.
    """
    n, C = last_seen.shape
    B = hist.shape[1]
    W = pos.shape[0]
    D = edge_up.shape[1]
    K = u_burst.shape[0]
    if interpret is None:
        interpret = default_interpret()
    bn = min(block_nodes, n)
    last_seen, hist, total, pad = pad_node_axis(bn, last_seen, hist, total)
    if pad:
        node_up = jnp.concatenate([node_up, jnp.zeros((pad,), bool)])
        edge_up = jnp.concatenate([edge_up, jnp.zeros((pad, D), bool)])
        u_nfail = jnp.concatenate([u_nfail, jnp.ones((pad,), u_nfail.dtype)])
        u_nrec = jnp.concatenate([u_nrec, jnp.ones((pad,), u_nrec.dtype)])
        sched_down = jnp.concatenate([sched_down, jnp.zeros((pad,), bool)])
        # pad edge-uniform rows with 1.0: never fails, never recovers
        e_fail = jnp.concatenate(
            [e_fail, jnp.ones((pad, D), e_fail.dtype)]
        )
        e_rec = jnp.concatenate([e_rec, jnp.ones((pad, D), e_rec.dtype)])
    npad = n + pad
    walk_spec = pl.BlockSpec((1, W), lambda p, i: (0, 0))
    wd_spec = pl.BlockSpec((D, W), lambda p, i: (0, 0))
    node_full_spec = pl.BlockSpec((1, npad), lambda p, i: (0, 0))
    edge_tile_spec = pl.BlockSpec((bn, D), lambda p, i: (i, 0))
    outs = pl.pallas_call(
        functools.partial(_whole_round_kernel, decafork_plus),
        grid=(2, npad // bn),
        in_specs=[
            pl.BlockSpec((1, 8), lambda p, i: (0, 0)),  # params_f
            pl.BlockSpec((1, 4), lambda p, i: (0, 0)),  # params_i
            walk_spec,  # pos
            walk_spec,  # track
            walk_spec,  # active
            walk_spec,  # u_move
            walk_spec,  # u_pfail
            walk_spec,  # u_fork
            walk_spec,  # u_term
            walk_spec,  # degrees_rows
            wd_spec,  # neighbors_rows
            wd_spec,  # edge_up_rows
            wd_spec,  # e_fail_rows
            wd_spec,  # e_rec_rows
            pl.BlockSpec((K, W), lambda p, i: (0, 0)),  # u_burst
            pl.BlockSpec((1, K), lambda p, i: (0, 0)),  # burst_sizes_eff
            node_full_spec,  # node_up
            node_full_spec,  # u_nfail
            node_full_spec,  # u_nrec
            node_full_spec,  # sched_down
            edge_tile_spec,  # edge_up tile
            edge_tile_spec,  # e_fail tile
            edge_tile_spec,  # e_rec tile
            pl.BlockSpec((bn, C), lambda p, i: (i, 0)),  # last_seen tile
            pl.BlockSpec((bn, B), lambda p, i: (i, 0)),  # hist tile
            pl.BlockSpec((bn, 1), lambda p, i: (i, 0)),  # total tile
        ],
        out_specs=[
            pl.BlockSpec((bn, C), lambda p, i: (i, 0)),  # last_seen
            pl.BlockSpec((bn, B), lambda p, i: (i, 0)),  # hist
            pl.BlockSpec((bn, 1), lambda p, i: (i, 0)),  # total
            edge_tile_spec,  # edge_up
            node_full_spec,  # node_up
            walk_spec,  # pos
            walk_spec,  # active
            walk_spec,  # theta
            walk_spec,  # chosen
            walk_spec,  # fork
            walk_spec,  # term
        ],
        out_shape=[
            jax.ShapeDtypeStruct((npad, C), last_seen.dtype),
            jax.ShapeDtypeStruct((npad, B), hist.dtype),
            jax.ShapeDtypeStruct((npad, 1), total.dtype),
            jax.ShapeDtypeStruct((npad, D), jnp.int32),
            jax.ShapeDtypeStruct((1, npad), jnp.int32),
            jax.ShapeDtypeStruct((1, W), pos.dtype),
            jax.ShapeDtypeStruct((1, W), jnp.int32),
            jax.ShapeDtypeStruct((1, W), jnp.float32),
            jax.ShapeDtypeStruct((1, W), jnp.int32),
            jax.ShapeDtypeStruct((1, W), jnp.int32),
            jax.ShapeDtypeStruct((1, W), jnp.int32),
        ],
        interpret=interpret,
    )(
        params_f,
        params_i,
        pos[None, :],
        track[None, :],
        active.astype(jnp.int32)[None, :],
        u_move[None, :],
        u_pfail[None, :],
        u_fork[None, :],
        u_term[None, :],
        degrees_rows.astype(jnp.int32)[None, :],
        neighbors_rows.astype(jnp.int32).T,
        edge_up_rows.astype(jnp.int32).T,
        e_fail_rows.astype(jnp.float32).T,
        e_rec_rows.astype(jnp.float32).T,
        u_burst,
        burst_sizes_eff[None, :],
        node_up.astype(jnp.int32)[None, :],
        u_nfail[None, :],
        u_nrec[None, :],
        sched_down.astype(jnp.int32)[None, :],
        edge_up.astype(jnp.int32),
        e_fail,
        e_rec,
        last_seen,
        hist,
        total[:, None],
    )
    (ls_o, hist_o, tot_o, eup_o, nup_o, pos_o, act_o, theta_o,
     chosen_o, fork_o, term_o) = outs
    return (
        ls_o[:n],
        hist_o[:n],
        tot_o[:n, 0],
        nup_o[0, :n] != 0,
        eup_o[:n] != 0,
        pos_o[0],
        act_o[0] != 0,
        theta_o[0],
        chosen_o[0] != 0,
        fork_o[0] != 0,
        term_o[0] != 0,
    )
