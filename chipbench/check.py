"""Decide ``correct``: what the timed path produced against the reference.

Per sampled trajectory (``studies.sample``) the reference replays the
whole trajectory from its key and the comparison reads:

  mismatch_rounds     rounds whose integer control plane (z, forks,
                      terms, failures; with a learning payload also
                      ``trained``, the local steps taken, which must be
                      one per live walk) differs from the reference, summed
                      over the sampled trajectories. Exact: limit 0. A
                      trajectory whose first difference falls on a round
                      the reference flags as a threshold tie is compared
                      up to that round only (``ties`` counts them);
  theta_mean_rel_gap  largest |program - reference| / max(reference, 1)
                      of the chosen walks' mean theta, over the rounds
                      compared before any difference (DecAFork family);
  loss_rel_gap        (learning payload) largest relative gap of a
                      trained replica's local loss over the first rounds
                      the reference replays (its initial model and first
                      local steps);
  first_loss_median_gap
                      the median, over every replica of the sampled
                      trajectories, of that relative gap in round 0,
                      where each replica still holds the initial model:
                      no optimizer step has turned rounding noise into
                      drift yet, and the median leaves out the odd
                      replica whose batch meets a bfloat16 rounding edge,
                      so a model kept in bfloat16 shows here while the
                      largest gap cannot tell it;
  fork_copy_gap       largest relative gap between the losses the program
                      reports for a forked child and its parent in the
                      round of the fork, over every fork the reference
                      makes in the rounds compared: the reference has the
                      child copy the parent's replica onto the parent's
                      node, so both step on the same model and batch and
                      their losses agree.

Each number is held to the limit the configuration's ``limits`` gives it.
"""
from __future__ import annotations

import numpy as np

CONTROL_PLANE = ("z", "forks", "terms", "failures")


def compare_trajectory(prog: dict, ref: dict) -> dict:
    """Numbers of one trajectory: ``prog`` and ``ref`` map field -> (steps,)."""
    diff = np.zeros(len(ref["z"]), bool)
    for f in CONTROL_PLANE + (("trained",) if "trained" in ref else ()):
        diff |= np.asarray(prog[f]) != np.asarray(ref[f])
    first = int(np.argmax(diff)) if diff.any() else len(diff)
    tie = bool(diff.any() and ref["tie"][first])
    nums = {
        "mismatch_rounds": 0 if tie else int(diff.sum()),
        "ties": int(tie),
        "compared_rounds": first,
    }
    if "theta_mean" in ref:
        r = np.asarray(ref["theta_mean"][:first], np.float64)
        p = np.asarray(prog["theta_mean"][:first], np.float64)
        nums["theta_mean_rel_gap"] = float(
            (np.abs(p - r) / np.maximum(np.abs(r), 1.0)).max(initial=0.0)
        )
    if "loss" in ref:
        k = min(first, len(ref["loss"]))
        on = np.asarray(ref["trained_mask"][:k])
        r = np.asarray(ref["loss"][:k], np.float64)[on]
        p = np.asarray(prog["loss"][:k], np.float64)[on]
        gap = np.abs(p - r) / np.abs(r)
        nums["loss_rel_gap"] = float(gap.max(initial=0.0))
        first_round = np.asarray(ref["trained_mask"][:1]).sum() if k else 0
        nums["first_loss_gaps"] = gap[:first_round]
    if "fork_pairs" in ref:
        pairs = np.asarray(ref["fork_pairs"]).reshape(-1, 3)
        loss = np.asarray(prog["loss"], np.float64)
        pairs = pairs[pairs[:, 0] < min(first, len(loss))]
        child, parent = loss[pairs[:, 0], pairs[:, 1]], loss[pairs[:, 0], pairs[:, 2]]
        nums["fork_copy_gap"] = float(
            (np.abs(child - parent) / np.abs(parent)).max(initial=0.0)
        )
        nums["forks_compared"] = len(pairs)
    return nums


def combine(per_trajectory: list) -> dict:
    """Sum the counts, take the largest gaps and the median first loss gap."""
    out: dict = {}
    firsts = []
    for nums in per_trajectory:
        for k, v in nums.items():
            if k == "first_loss_gaps":
                firsts.append(v)
            elif k.endswith("_gap"):
                out[k] = max(out.get(k, 0.0), v)
            else:
                out[k] = out.get(k, 0) + v
    if firsts:
        gaps = np.concatenate(firsts)
        out["first_loss_median_gap"] = float(np.median(gaps)) if gaps.size else 0.0
    return out


def verdict(numbers: dict, limits: dict) -> tuple:
    """``(correct, compared)`` where ``compared`` maps each number that
    has a limit to ``{"value", "limit"}``; a number is within its limit
    when it is at most the limit."""
    compared = {
        k: {"value": numbers[k], "limit": limits[k]} for k in limits if k in numbers
    }
    ok = bool(compared) and all(c["value"] <= c["limit"] for c in compared.values())
    return ok, compared
