"""Decision rules: DECAFORK, DECAFORK+ and the MISSINGPERSON baseline.

All rules are pure functions of (estimates, thresholds, PRNG key) returning
boolean event masks; the simulator executes the resulting forks and
terminations via the slot machinery in ``walkers.py``. Rules fire only for
"chosen" walks — per paper footnote 6, a node visited by several walks
runs the procedure for exactly one of them (we pick the lowest slot index).

``ProtocolConfig`` is a registered jax pytree split into
  - *traced data leaves* — the numeric knobs (``z0``, ``eps``, ``eps2``,
    ``eps_mp``, ``fork_prob``, ``protocol_start``, quantiles): jax values
    that vmap/batch across scenarios without recompiling;
  - *static aux fields* — everything that determines program shape or
    branching (``algorithm``, ``max_walks``, ``rt_bins``,
    ``estimator_impl``, ``auto_eps``, ``analytic_survival``,
    ``theta_bin_width``): two configs differing here have different pytree
    structures and therefore different compiled programs.

This split is what lets the sweep engine (``repro.sweep``) run a whole
epsilon grid / failure-regime stack as ONE jit-compiled call.
"""
from __future__ import annotations

import dataclasses
import numbers

import jax
import jax.numpy as jnp

from repro.core.estimator import NEVER
from repro.core.failures import _canonical_leaf

ALGORITHMS = ("none", "missingperson", "decafork", "decafork+")

# numeric, jax-traceable knobs (pytree data leaves, batchable under vmap)
_PROTOCOL_DATA = (
    "z0",
    "eps",
    "eps2",
    "eps_mp",
    "fork_prob",
    "protocol_start",
    "eps_quantile",
    "eps2_quantile",
    "auto_min_samples",
    "p_jump",
    "bias_p",
    "bias_q",
)
# shape/branch-determining fields (pytree aux data, static under jit)
_PROTOCOL_META = (
    "algorithm",
    "max_walks",
    "rt_bins",
    "analytic_survival",
    "estimator_impl",
    "auto_eps",
    "theta_bin_width",
    "round_impl",
    "walk_variant",
    "bloom_bits",
)

ROUND_IMPLS = ("auto", "fused", "unfused")

# movement strategies (repro.zoo.variants implements the non-uniform ones):
#   'uniform' — the paper's walk, a uniform available neighbor (default;
#       compiles the identical pre-zoo program);
#   'jump'    — w.p. p_jump teleport to a uniform up-node (Liu et al.,
#       random walks with jumps — escapes partitions and slow mixing);
#   'biased'  — node2vec-style p/q second-order walk (needs the walk's
#       previous position, carried as a WalkState column);
#   'bloom'   — self-avoiding walk with a per-walk Bloom-filter history
#       (fixed bloom_bits bit array; forked with the slot).
WALK_VARIANTS = ("uniform", "jump", "biased", "bloom")


@dataclasses.dataclass(frozen=True, eq=False)
class ProtocolConfig:
    """Protocol parameters; see module docstring for the static/traced split."""

    algorithm: str = "decafork"
    z0: int | jax.Array = 10  # target number of walks Z_0
    max_walks: int = 40  # walk slot capacity W (>= z0), static
    eps: float | jax.Array = 2.0  # forking threshold (theta_hat < eps)
    eps2: float | jax.Array = 5.75  # termination threshold, DECAFORK+
    eps_mp: float | jax.Array = 300.0  # MISSINGPERSON timeout
    fork_prob: float | jax.Array | None = None  # p; defaults to 1/z0
    rt_bins: int = 1024  # return-time histogram resolution, static
    protocol_start: int | jax.Array = 0  # no decisions before this step
    analytic_survival: bool = False  # footnote 5: geometric survival from pi
    # 'gather' (row-restricted cumsum+gather) | 'compare' (dense compare-
    # accumulate) | 'pallas' (theta_survival kernel) | 'fused' (one
    # round_update pass: scatter+max+sums) | 'auto' (best per backend:
    # gather on CPU/GPU, fused on TPU, where the whole-round kernel runs)
    estimator_impl: str = "auto"
    # ---- beyond-paper: self-calibrating thresholds ----------------------
    # The paper hand-tunes eps per graph (Fig. 4 uses eps in {1.85,2,2.1})
    # and its Irwin-Hall rule ignores the inspection-paradox bias
    # (EXPERIMENTS.md "Estimator bias"). With auto_eps every node records
    # its own theta-hat distribution during the warmup phase and sets its
    # fork/terminate thresholds as LOCAL quantiles of that distribution —
    # decentralized (Rule 1), bias-inclusive, and graph-agnostic.
    auto_eps: bool = False
    eps_quantile: float | jax.Array = 0.05  # fork below this warmup quantile
    eps2_quantile: float | jax.Array = 0.995  # terminate above this quantile
    theta_bin_width: float = 0.25  # histogram bin width, static (shapes)
    auto_min_samples: int | jax.Array = 50  # below: fall back to eps/eps2
    # 'fused' (whole-round single pass: hop + topology + failures +
    # decisions in one dispatch) | 'unfused' (the literal per-stage
    # sequence — the bitwise oracle) | 'auto' (best per backend,
    # REPRO_ROUND_IMPL env override honored). Static (program shape).
    round_impl: str = "auto"
    # ---- zoo walk variants (repro.zoo): movement strategy ---------------
    walk_variant: str = "uniform"  # see WALK_VARIANTS; static (program)
    p_jump: float | jax.Array = 0.0  # 'jump': teleport prob per step
    bias_p: float | jax.Array = 1.0  # 'biased': return parameter p
    bias_q: float | jax.Array = 1.0  # 'biased': in-out parameter q
    bloom_bits: int = 64  # 'bloom': per-walk filter width, static

    def __post_init__(self):
        if self.algorithm not in ALGORITHMS:
            raise ValueError(f"unknown algorithm {self.algorithm!r}")
        if self.round_impl not in ROUND_IMPLS:
            raise ValueError(
                f"unknown round_impl {self.round_impl!r}; "
                f"expected one of {ROUND_IMPLS}"
            )
        if self.walk_variant not in WALK_VARIANTS:
            raise ValueError(
                f"unknown walk_variant {self.walk_variant!r}; "
                f"expected one of {WALK_VARIANTS}"
            )
        # traced z0 values defer this check to the caller (sweep stacks
        # validate statically before batching)
        if isinstance(self.z0, numbers.Integral) and self.max_walks < self.z0:
            raise ValueError("max_walks must be >= z0")

    @property
    def p(self):
        return self.fork_prob if self.fork_prob is not None else 1.0 / self.z0

    @property
    def static_fields(self) -> tuple:
        """The hashable program-shape signature of this config."""
        return tuple(getattr(self, f) for f in _PROTOCOL_META)

    # value-based eq/hash over all fields (concrete array leaves fold to
    # tuples; traced configs raise, as any tracer-hash must)
    def _canonical(self) -> tuple:
        return tuple(
            _canonical_leaf(getattr(self, f))
            for f in _PROTOCOL_DATA + _PROTOCOL_META
        )

    def __eq__(self, other):
        if not isinstance(other, ProtocolConfig):
            return NotImplemented
        return self._canonical() == other._canonical()

    def __hash__(self):
        return hash(self._canonical())


def _protocol_flatten(cfg: ProtocolConfig):
    data = tuple(getattr(cfg, f) for f in _PROTOCOL_DATA)
    aux = tuple(getattr(cfg, f) for f in _PROTOCOL_META)
    return data, aux


def _protocol_unflatten(aux, children) -> ProtocolConfig:
    # bypass __init__/__post_init__: jax may unflatten with placeholder
    # leaves (tracers, avals, bare object()), which must round-trip as-is
    cfg = object.__new__(ProtocolConfig)
    for f, v in zip(_PROTOCOL_DATA, children):
        object.__setattr__(cfg, f, v)
    for f, v in zip(_PROTOCOL_META, aux):
        object.__setattr__(cfg, f, v)
    return cfg


jax.tree_util.register_pytree_node(
    ProtocolConfig, _protocol_flatten, _protocol_unflatten
)


def choose_walks(pos: jax.Array, active: jax.Array, n_nodes: int) -> jax.Array:
    """Footnote 6: per node, select the single lowest-index visiting walk.

    Returns (W,) bool mask of walks that run the protocol this step.
    """
    W = pos.shape[0]
    slots = jnp.arange(W, dtype=jnp.int32)
    cand = jnp.where(active, slots, W)
    best = jnp.full((n_nodes,), W, jnp.int32).at[pos].min(cand, mode="drop")
    return active & (best[pos] == slots)


def choose_walks_pairwise(pos: jax.Array, active: jax.Array) -> jax.Array:
    """``choose_walks`` without the (n,)-sized scatter: each walk takes the
    min candidate slot over the walks sharing its node, via a (W, W)
    compare. Bitwise-identical — for an active walk, the set minimized
    over is exactly the candidates scattered to its node (inactive
    co-located walks contribute the same sentinel W either way) — but
    every array is walk-sized, which is what the fused whole-round path
    needs (W*W tiny; no n-sized intermediate, no scatter).
    """
    W = pos.shape[0]
    slots = jnp.arange(W, dtype=jnp.int32)
    cand = jnp.where(active, slots, W)
    same = pos[:, None] == pos[None, :]
    best = jnp.min(jnp.where(same, cand[None, :], W), axis=1)
    return active & (best == slots)


def decafork_decisions(
    theta: jax.Array,  # (W,) theta-hat per walk
    chosen: jax.Array,  # (W,) bool
    key: jax.Array,
    cfg: ProtocolConfig,
    enabled: jax.Array,  # scalar bool: t >= protocol_start
    eps: jax.Array | float | None = None,  # per-walk override (auto_eps)
    eps2: jax.Array | float | None = None,
):
    """DECAFORK fork mask (and DECAFORK+ termination mask)."""
    eps = cfg.eps if eps is None else eps
    eps2 = cfg.eps2 if eps2 is None else eps2
    k_fork, k_term = jax.random.split(key)
    u_fork = jax.random.uniform(k_fork, theta.shape)
    fork = chosen & (theta < eps) & (u_fork < cfg.p) & enabled
    if cfg.algorithm == "decafork+":
        u_term = jax.random.uniform(k_term, theta.shape)
        term = chosen & (theta > eps2) & (u_term < cfg.p) & enabled
        # eps < eps2 makes these disjoint, but guard anyway
        term = term & ~fork
    else:
        term = jnp.zeros_like(fork)
    return fork, term


def theta_quantile_thresholds(
    theta_hist: jax.Array,  # (n, TB) per-node warmup theta-hat histogram
    pos: jax.Array,  # (W,) node per walk
    cfg: ProtocolConfig,
):
    """Per-walk (eps, eps2) from the visiting node's own theta-hat
    distribution (auto_eps mode). Nodes with too few warmup samples fall
    back to the configured global thresholds."""
    rows = theta_hist[pos]  # (W, TB)
    total = jnp.sum(rows, axis=1, keepdims=True)
    cdf = jnp.cumsum(rows, axis=1) / jnp.maximum(total, 1.0)
    TB = rows.shape[1]
    centers = (jnp.arange(TB, dtype=jnp.float32) + 0.5) * cfg.theta_bin_width

    def quantile(q):
        ok = cdf >= q
        idx = jnp.argmax(ok, axis=1)  # first bin reaching the quantile
        return centers[idx]

    eps_local = quantile(cfg.eps_quantile)
    eps2_local = quantile(cfg.eps2_quantile)
    have = total[:, 0] >= cfg.auto_min_samples
    eps = jnp.where(have, eps_local, cfg.eps)
    eps2 = jnp.where(have, eps2_local, cfg.eps2)
    return eps, eps2


def missingperson_decisions(
    last_seen: jax.Array,  # (n, C) int32
    pos: jax.Array,  # (W,)
    track: jax.Array,  # (W,)
    chosen: jax.Array,  # (W,)
    t: jax.Array,
    key: jax.Array,
    cfg: ProtocolConfig,
    enabled: jax.Array,
) -> jax.Array:
    """MISSINGPERSON: (W, C) mask of replacement-fork events.

    Event (k, l) means: the node visited by walk k deems initial id l
    missing (unseen for > eps_mp) and forks a duplicate of k carrying
    identifier l "in replacement of RW l". Columns are the full track
    space C (= W); only the initial-id columns l < z0 can fire, expressed
    as a mask so that ``z0`` stays a traced (batchable) value.
    """
    W = pos.shape[0]
    C = last_seen.shape[1]
    ls = last_seen[pos]  # (W, C)
    stale = (t - ls) > cfg.eps_mp
    ids = jnp.arange(C, dtype=jnp.int32)[None, :]
    is_initial = ids < cfg.z0
    not_self = ids != track[:, None]
    u = jax.random.uniform(key, (W, C))
    return (
        chosen[:, None] & stale & is_initial & not_self & (u < cfg.p) & enabled
    )
