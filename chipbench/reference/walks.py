"""Plain reference of one DecAFork / DecAFork+ / MissingPerson trajectory.

Written from the paper's rules (arXiv 2407.11762, Sec. III) and the
round order the program documents, in NumPy, one round at a time:

  1. every live walk hops to a uniform neighbour;
  2. scheduled bursts kill ``size`` live walks of lowest random score;
  3. every live walk's node records the walk's return time and last
     visit; per node the lowest-slot visitor is chosen;
  4. the chosen walk's node estimates the live walks,
     theta = 1/2 + sum over other seen walks c of S(t - L_c), with S the
     node's empirical return-time survival (Eq. 1), and forks (theta <
     eps) or, for DecAFork+, terminates (theta > eps2) with probability
     1/z0; MissingPerson instead replaces each initial walk unseen for
     more than eps_mp rounds;
  5. terminations free slots, forks take the lowest free slots in slot
     order.

theta is exact: n_valid - 1/2 - M/T from integer counts, in float64. A
decision whose theta lies within ``TIE_ULPS`` float32 steps of its
threshold is a tie the program may round either way; the round is
flagged (``tie``) and the check stops comparing that trajectory there.
``precision="bfloat16"`` computes theta (MissingPerson: the elapsed time)
in bfloat16: the control that a correct check must reject.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np

from chipbench.reference import draws

NEVER = -1
TIE_ULPS = 16
BF16 = ml_dtypes.bfloat16


def _near(x, thr):
    return np.abs(x - thr) <= TIE_ULPS * np.spacing(np.float32(thr))


def trajectory(
    neighbors: np.ndarray, proto: dict, failures: dict, steps: int,
    base_key: int, seeds: int, index: int, precision: str = "float32",
    on_round=None,
) -> dict:
    """Per-round ``z, forks, terms, failures, theta_mean, tie`` of
    trajectory ``index`` of the ensemble ``(base_key, seeds)``.

    ``on_round(t, pos, active, fork_parent)``, if given, sees every
    round's end state: where each slot's walk is, which slots are live,
    and the parent slot of a walk forked into a slot this round (-1
    elsewhere); this is what a walk's payload follows."""
    n, D = neighbors.shape
    W, z0, B = proto["max_walks"], proto["z0"], proto["rt_bins"]
    alg = proto["algorithm"]
    grid = alg == "missingperson"
    low = precision == "bfloat16"
    p = np.float32(1) / np.float32(z0)
    enabled_from = proto["protocol_start"]
    bursts = list(zip(failures["burst_times"], failures["burst_sizes"]))

    _, k_init, k_run = draws.trajectory_key(base_key, seeds, index)
    streams = draws.Streams(k_run, W, z0 if grid else 0)
    slots = np.arange(W)
    pos = draws.start_positions(k_init, W, n).astype(np.int64)
    active = slots < z0
    track = slots.copy()
    if grid:
        ls = np.where(slots[None, :] < z0, 0, NEVER) * np.ones((n, 1), np.int64)
    else:
        ls = np.full((n, W), NEVER, np.int64)
        ls[pos[active], slots[active]] = 0
        cum = np.zeros((n, B + 1), np.int64)  # cum[i, k]: samples with min(r, B) <= k

    out = {f: np.zeros(steps, np.int64) for f in ("z", "forks", "terms", "failures")}
    if not grid:  # MissingPerson estimates nothing
        out["theta_mean"] = np.zeros(steps)
    out["tie"] = np.zeros(steps, bool)
    for t in range(steps):
        u_move, dec = streams.at(t)
        n_before = int(active.sum())
        hop = np.minimum((u_move * np.float32(D)).astype(np.int32), D - 1)
        pos = np.where(active, neighbors[pos, hop], pos)
        for i, (bt, size) in enumerate(bursts):
            if t == bt:
                score = np.where(active, streams.burst(t, i), np.inf)
                rank = (score[:, None] > score[None, :]).sum(1)
                active = active & ~(rank < size)
        out["failures"][t] = n_before - int(active.sum())

        live = np.flatnonzero(active)
        if not grid:
            prev = ls[pos[live], track[live]]
            r = t - prev
            for k, rk in zip(live[(prev != NEVER) & (r >= 1)], r[(prev != NEVER) & (r >= 1)]):
                cum[pos[k], min(rk, B):] += 1
        ls[pos[live], track[live]] = t
        _, first = np.unique(pos[live], return_index=True)
        chosen = np.sort(live[first])
        on = t >= enabled_from
        terms = np.zeros(0, np.int64)
        if grid:
            rows = ls[pos[chosen], :z0]
            elapsed = t - rows
            if low:
                elapsed = BF16(t) - rows.astype(BF16)
            ev = (
                (elapsed > np.float32(proto["eps_mp"]))
                & (np.arange(z0)[None, :] != track[chosen][:, None])
                & (dec[chosen] < p)
                & on
            )
            parents, ids = chosen[np.nonzero(ev)[0]], np.nonzero(ev)[1]
        else:
            rows = ls[pos[chosen]]
            seen = rows != NEVER
            rr = np.minimum(np.where(seen, t - rows, 0), B)
            mass = np.take_along_axis(cum[pos[chosen]], rr, axis=1).sum(1)
            total = cum[pos[chosen], B]
            n_valid = seen.sum(1)
            theta = n_valid - 0.5 - np.where(total > 0, mass / np.maximum(total, 1), 0.0)
            used = theta
            if low:
                q = np.where(
                    total > 0, mass.astype(BF16) / np.maximum(total, 1).astype(BF16), BF16(0)
                ).astype(BF16)
                used = ((n_valid.astype(BF16) - q).astype(BF16) - BF16(0.5)).astype(np.float64)
            eps = float(np.float32(proto["eps"]))
            coin = (dec[0][chosen] < p) & on
            fork = (used < eps) & coin
            tie = bool((coin & _near(theta, eps)).any())
            if alg == "decafork+":
                eps2 = float(np.float32(proto["eps2"]))
                coin2 = (dec[1][chosen] < p) & on
                term = (used > eps2) & coin2 & ~fork
                tie |= bool((coin2 & _near(theta, eps2)).any())
                terms = chosen[term]
                active[terms] = False
            out["tie"][t] = tie
            out["theta_mean"][t] = used.mean() if chosen.size else 0.0
            parents, ids = chosen[fork], None

        free = np.flatnonzero(~active)
        m = min(len(parents), len(free))
        fork_parent = np.full(W, -1)
        for j in range(m):
            e, s = parents[j], free[j]
            active[s], pos[s], fork_parent[s] = True, pos[e], e
            if ids is None:  # DecAFork: the child is a fresh walk of its own slot
                track[s] = s
                ls[:, s] = NEVER
                ls[pos[e], s] = t
            else:  # MissingPerson: the child carries the missing walk's id
                track[s] = ids[j]
        out["forks"][t] = m
        out["terms"][t] = len(terms)
        out["z"][t] = int(active.sum())
        if on_round is not None:
            on_round(t, pos, active, fork_parent)
    return out
