"""Return-time estimation and the theta-hat walk-count estimator (Eq. 1).

This is the heart of the paper: every node i maintains
  - ``last_seen[i, c]``: last time step at which walk (track) c visited i
    (-1 if never seen) — the random variable L_{i,c}(t);
  - ``hist[i, b]``: empirical histogram of observed return times R_i
    (bin b holds counts of return time b+1, the final bin clamps the tail).

From the histogram each node derives the empirical survival function
  S_i(r) = Pr(R_i > r) = 1 - F_hat_{R_i}(r)
and estimates the number of live walks as (Eq. 1)
  theta_hat_i(t) = 1/2 + sum_{c != k, seen} S_i(t - last_seen[i, c]).

Everything here is functional and jit/vmap-friendly: histograms are dense
(n, B) int16 count arrays (exact — per-node-per-bin counts are bounded by
the step budget, far below 32767; totals are int32 since W*steps can
exceed int16), survival evaluation is a gather into the exclusive
cumulative sum (widened to float32 at the read), and theta-hat is a
masked (W, C) reduction.

The fused whole-round path carries ``CumulativeReturnState`` instead: the
(n, B+1) cumulative count table updated incrementally by scatter-adding
step rows, which removes the per-round cumsum from the hot loop entirely
(XLA CPU lowers ``cumsum`` to a quadratic reduce-window — it dominated
the PR-4 round). Integer counts make the two carries exact transforms of
each other: ``hist = diff(cum)``, ``total = cum[:, -1]``.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

NEVER = -1  # sentinel for "walk never seen at this node"


class ReturnTimeState(NamedTuple):
    """Per-node empirical return-time statistics.

    Counts are exact integers: per-bin counts fit int16 (bounded by the
    step budget — a node observes at most ``steps`` samples overall, let
    alone per bin), totals are int32 (W * steps can exceed 32767). All
    reads widen to float32, where every count is exactly representable
    (far below 2**24), so the narrow carry is bitwise-neutral downstream.
    """

    hist: jax.Array  # (n, B) int16 counts; bin b <-> return time b+1
    total: jax.Array  # (n,) int32 total sample count


def init_return_time_state(n: int, bins: int) -> ReturnTimeState:
    return ReturnTimeState(
        hist=jnp.zeros((n, bins), jnp.int16),
        total=jnp.zeros((n,), jnp.int32),
    )


def record_returns(
    state: ReturnTimeState,
    nodes: jax.Array,  # (W,) int32 node visited by each walk
    r: jax.Array,  # (W,) int32 observed return times (t - last_seen)
    valid: jax.Array,  # (W,) bool — active walk with a prior visit record
) -> ReturnTimeState:
    """Scatter-add observed return-time samples into per-node histograms.

    Dtype-polymorphic (follows ``state``): the benchmark grid keeps a
    float32 arm alive for measurement, the simulator carries int16/int32.
    """
    bins = state.hist.shape[1]
    b = jnp.clip(r, 1, bins) - 1
    hist = state.hist.at[nodes, b].add(
        valid.astype(state.hist.dtype), mode="drop"
    )
    total = state.total.at[nodes].add(
        valid.astype(state.total.dtype), mode="drop"
    )
    return ReturnTimeState(hist=hist, total=total)


def survival_cumulative(state: ReturnTimeState) -> jax.Array:
    """(n, B+1) table C with C[i, r] = #samples <= r (C[i, 0] = 0)."""
    csum = jnp.cumsum(state.hist.astype(jnp.float32), axis=1)
    return jnp.concatenate([jnp.zeros_like(csum[:, :1]), csum], axis=1)


def survival_eval(
    cum: jax.Array,  # (n, B+1) from survival_cumulative
    total: jax.Array,  # (n,)
    nodes: jax.Array,  # (...,) int32
    r: jax.Array,  # (...,) int32 elapsed times
) -> jax.Array:
    """Empirical S_i(r) = 1 - F_hat(r), elementwise over broadcasted args.

    Conventions: S(r <= 0) = 1; nodes with no samples yet return 1
    (optimistic prior — a walk is presumed alive absent any evidence).
    """
    bins = cum.shape[1] - 1
    r_cl = jnp.clip(r, 0, bins)
    tot = total[nodes].astype(jnp.float32)
    seen_mass = cum[nodes, r_cl]
    s = 1.0 - seen_mass / jnp.maximum(tot, 1.0)
    s = jnp.where(tot > 0, s, 1.0)
    return jnp.where(r <= 0, 1.0, s)


def analytic_survival_eval(
    pi: jax.Array,  # (n,) stationary distribution (geometric rate q_i = pi_i)
    nodes: jax.Array,
    r: jax.Array,
) -> jax.Array:
    """Analytic geometric survival S_i(r) = (1 - pi_i)^r (footnote 5)."""
    q = pi[nodes]
    s = jnp.exp(jnp.log1p(-q) * r.astype(jnp.float32))
    return jnp.where(r <= 0, 1.0, s)


def theta_hat(
    last_seen: jax.Array,  # (n, C) int32
    cum: jax.Array,  # (n, B+1)
    total: jax.Array,  # (n,)
    t: jax.Array,  # scalar int32 current time
    pos: jax.Array,  # (W,) node of each visiting walk
    track: jax.Array,  # (W,) column owned by each walk
    *,
    pi: jax.Array | None = None,  # if set, use analytic survival instead
) -> jax.Array:
    """Eq. (1): theta_hat for every walk slot's current node, vectorized.

    Returns (W,) theta values; caller masks by which walks were "chosen"
    by their node. The visiting walk's own column is excluded (it
    contributes the deterministic 1/2 offset).
    """
    W = pos.shape[0]
    C = last_seen.shape[1]
    ls = last_seen[pos]  # (W, C)
    elapsed = t - ls  # (W, C)
    nodes_b = jnp.broadcast_to(pos[:, None], (W, C))
    if pi is not None:
        s = analytic_survival_eval(pi, nodes_b, elapsed)
    else:
        s = survival_eval(cum, total, nodes_b, elapsed)
    cols = jnp.arange(C)[None, :]
    mask = (ls != NEVER) & (cols != track[:, None])
    return 0.5 + jnp.sum(jnp.where(mask, s, 0.0), axis=1)


def theta_hat_rows(
    last_seen: jax.Array,  # (n, C) int32
    hist: jax.Array,  # (n, B)
    total: jax.Array,  # (n,)
    t: jax.Array,  # scalar int32 current time
    pos: jax.Array,  # (W,) node of each visiting walk
    track: jax.Array,  # (W,) column owned by each walk
    *,
    pi: jax.Array | None = None,  # if set, use analytic survival instead
    max_elapsed: int | None = None,  # static upper bound on t (see below)
) -> jax.Array:
    """Row-restricted Eq. (1): gather the <= W visited rows FIRST, then
    run the cumsum + survival lookup on those rows only.

    Bitwise-identical to ``theta_hat(last_seen, survival_cumulative(rts),
    ...)`` — per-row cumsums and the elementwise survival evaluation do
    not depend on the other rows — but the per-round work drops from
    O(n*B) (full cumulative table every round) to O(W*B): proportional
    to the walks actually observing, not the graph. This is the default
    ``estimator_impl="gather"`` hot path.

    ``max_elapsed`` (static) is an upper bound on ``t`` over the whole
    run (the simulator passes its ``steps``): no elapsed time — and so
    no cumulative-table lookup index — can exceed it, so the per-row
    cumsum is trimmed to ``min(B, max_elapsed)`` bins. Prefix sums at
    the surviving indices do not involve the trimmed tail, so the
    result stays bitwise identical while a short run over a
    high-resolution histogram (steps < rt_bins) skips the dead tail's
    work entirely.
    """
    W = pos.shape[0]
    C = last_seen.shape[1]
    ls = last_seen[pos]  # (W, C)
    elapsed = t - ls  # (W, C)
    if pi is not None:
        nodes_b = jnp.broadcast_to(pos[:, None], (W, C))
        s = analytic_survival_eval(pi, nodes_b, elapsed)
    else:
        bins = hist.shape[1]
        if max_elapsed is not None:
            bins = min(bins, max(int(max_elapsed), 1))
        csum = jnp.cumsum(
            hist[pos][:, :bins].astype(jnp.float32), axis=1
        )  # visited rows only
        cum = jnp.concatenate([jnp.zeros_like(csum[:, :1]), csum], axis=1)
        r_cl = jnp.clip(elapsed, 0, bins)
        tot = jnp.broadcast_to(
            total[pos].astype(jnp.float32)[:, None], (W, C)
        )
        seen_mass = jnp.take_along_axis(cum, r_cl, axis=1)
        s = 1.0 - seen_mass / jnp.maximum(tot, 1.0)
        s = jnp.where(tot > 0, s, 1.0)
        s = jnp.where(elapsed <= 0, 1.0, s)
    cols = jnp.arange(C)[None, :]
    mask = (ls != NEVER) & (cols != track[:, None])
    return 0.5 + jnp.sum(jnp.where(mask, s, 0.0), axis=1)


def survival_node_sums_rows(
    last_seen: jax.Array,  # (R, C) — any row block (full table or a tile)
    hist: jax.Array,  # (R, B)
    total: jax.Array,  # (R,)
    t: jax.Array,
) -> jax.Array:
    """The compare-accumulate survival core: sum_c S_i(t - L_{i,c}) per
    row, no gather — cum_i(r) = sum_b hist[i,b] [r > b].

    This is THE single source of the formula: ``node_sums_compare`` calls
    it on the full node table, and the Pallas kernels
    (``kernels/theta_survival.py``, ``kernels/round_update.py``) call it
    on their VMEM-resident node tiles — one implementation, so the
    survival conventions (optimistic no-sample prior, S(r<=0)=1 via the
    r=0 clamp) can never drift between the jnp oracle and the kernels.
    Plain jnp on arrays; traceable inside and outside kernel bodies.
    """
    R, C = last_seen.shape
    B = hist.shape[1]
    hist_f = hist.astype(jnp.float32)  # exact: integer counts < 2**24
    total_f = total.astype(jnp.float32)
    valid = last_seen != NEVER
    # unseen columns clamp to r=0, which no bin index (>= 0) is below, so
    # the compare alone masks them: no (R, C, B) bool broadcast of
    # ``valid``, which Mosaic cannot lay out
    r = jnp.where(valid, t - last_seen, 0)  # (R, C)
    bidx = jax.lax.broadcasted_iota(jnp.int32, (R, C, B), 2)
    over = r[:, :, None] > bidx
    cnt = jnp.sum(over.astype(jnp.float32), axis=1)  # (R, B)
    mass = jnp.sum(cnt * hist_f, axis=1)
    n_valid = jnp.sum(valid.astype(jnp.float32), axis=1)
    s = n_valid - mass / jnp.maximum(total_f, 1.0)
    return jnp.where(total_f > 0, s, n_valid)


def node_sums_compare(
    last_seen: jax.Array,  # (n, C)
    hist: jax.Array,  # (n, B)
    total: jax.Array,  # (n,)
    t: jax.Array,
) -> jax.Array:
    """sum_c S_i(t - L_{i,c}) per node via the TPU compare-accumulate
    formulation (``survival_node_sums_rows`` on the full table); exists
    in pure jnp both as the kernel oracle and as a measurable CPU/XLA
    variant."""
    return survival_node_sums_rows(last_seen, hist, total, t)


def theta_hat_from_node_sums(node_sums: jax.Array, pos: jax.Array) -> jax.Array:
    """theta for a visiting walk = node_sum - 1 (own fresh column, S=1)
    + 1/2 (deterministic self term) = node_sum - 1/2.

    Valid only AFTER last_seen[pos, track] was updated to t.
    """
    return node_sums[pos] - 0.5


# --- incremental cumulative carry (fused whole-round hot path) -----------


class CumulativeReturnState(NamedTuple):
    """Per-node cumulative return-time counts, carried incrementally.

    ``cum[i, r] = #samples at node i with return time <= r`` for
    r in 0..C (so ``cum[:, 0] == 0`` and ``cum[:, -1]`` is the total
    sample count: every sample's clamped bin ``clip(r, 1, B) - 1`` lies
    below ``C = min(B, steps)`` because observed return times never
    exceed the step budget). This is exactly the table
    ``theta_hat_rows`` rebuilds from the histogram with a per-round
    cumsum; carrying it directly turns each observation into a
    scatter-add of (W, C+1) 0/1 step rows and removes the cumsum —
    XLA CPU's quadratic reduce-window — from the round entirely.
    int32 throughout: the last column is total-bounded (W * steps).
    """

    cum: jax.Array  # (n, C+1) int32 cumulative counts


def init_cumulative_state(n: int, bins: int) -> CumulativeReturnState:
    """``bins`` here is the TRIMMED bin count C = min(rt_bins, steps)."""
    return CumulativeReturnState(cum=jnp.zeros((n, bins + 1), jnp.int32))


def record_returns_cumulative(
    state: CumulativeReturnState,
    nodes: jax.Array,  # (W,) int32 node visited by each walk
    r: jax.Array,  # (W,) int32 observed return times (t - last_seen)
    valid: jax.Array,  # (W,) bool — active walk with a prior visit record
    bins: int,  # the FULL histogram bin count B (clamp target)
) -> CumulativeReturnState:
    """Scatter-add the step rows ``[col > b]`` — the cumulative image of
    ``record_returns``'s one-hot at bin ``b = clip(r, 1, B) - 1``.

    Exact-integer equivalent of ``record_returns`` on the cumulative
    table: ``diff(cum)`` after this update equals ``hist`` after that
    one, bin for bin.
    """
    b = jnp.clip(r, 1, bins) - 1  # (W,) same clamp as record_returns
    cols = jnp.arange(state.cum.shape[1], dtype=b.dtype)[None, :]
    rows = ((cols > b[:, None]) & valid[:, None]).astype(state.cum.dtype)
    return CumulativeReturnState(
        cum=state.cum.at[nodes].add(rows, mode="drop")
    )


def cumulative_to_return_time(
    state: CumulativeReturnState, bins: int
) -> ReturnTimeState:
    """Exact inverse transform: ``hist = diff(cum)`` (zero-padded back to
    the full ``bins``), ``total = cum[:, -1]``. Bitwise the histogram
    ``record_returns`` would have accumulated from the same samples."""
    cum = state.cum
    hist = (cum[:, 1:] - cum[:, :-1]).astype(jnp.int16)
    c = hist.shape[1]
    if c < bins:
        hist = jnp.pad(hist, ((0, 0), (0, bins - c)))
    return ReturnTimeState(hist=hist, total=cum[:, -1])


def theta_hat_cumulative(
    last_seen: jax.Array,  # (n, C) int32
    state: CumulativeReturnState,
    t: jax.Array,  # scalar int32 current time
    pos: jax.Array,  # (W,) node of each visiting walk
    track: jax.Array,  # (W,) column owned by each walk
    *,
    pi: jax.Array | None = None,  # if set, use analytic survival instead
) -> jax.Array:
    """Eq. (1) read directly off the carried cumulative table.

    Bitwise-identical to ``theta_hat_rows(..., max_elapsed=steps)`` when
    the carry was trimmed to ``min(B, steps)`` bins: the gathered int32
    prefix counts cast exactly to the float32 values the per-round
    cumsum would produce (all counts < 2**24), and the survival tail is
    the same expression. No cumsum anywhere — the dominant cost of the
    gather-family round is gone.
    """
    W = pos.shape[0]
    C = last_seen.shape[1]
    ls = last_seen[pos]  # (W, C)
    elapsed = t - ls  # (W, C)
    if pi is not None:
        nodes_b = jnp.broadcast_to(pos[:, None], (W, C))
        s = analytic_survival_eval(pi, nodes_b, elapsed)
    else:
        cum = state.cum[pos]  # (W, bins+1) int32 — visited rows only
        bins = cum.shape[1] - 1
        r_cl = jnp.clip(elapsed, 0, bins)
        seen_mass = jnp.take_along_axis(cum, r_cl, axis=1).astype(
            jnp.float32
        )
        tot = jnp.broadcast_to(
            cum[:, -1:].astype(jnp.float32), (W, C)
        )
        s = 1.0 - seen_mass / jnp.maximum(tot, 1.0)
        s = jnp.where(tot > 0, s, 1.0)
        s = jnp.where(elapsed <= 0, 1.0, s)
    cols = jnp.arange(C)[None, :]
    mask = (ls != NEVER) & (cols != track[:, None])
    return 0.5 + jnp.sum(jnp.where(mask, s, 0.0), axis=1)
