"""Fully-jitted multi-walk simulator (the paper's evaluation engine).

One synchronous round (time t -> t+1):
  1. the topology evolves (``GraphState``: scheduled/i.i.d. node crashes,
     i.i.d. link failures, stochastic recoveries); a crashing node kills
     the walks resident on it;
  2. every surviving walk hops to a uniform random *available* neighbor
     (down nodes/links are unreachable; a stranded walk holds position);
  3. walk-level failures strike (probabilistic, burst, Byzantine —
     Section II; Pac-Man absorption);
  4. each node visited by >= 1 surviving walk "chooses one" (footnote 6),
     records return-time samples for *all* visitors, updates last-seen;
  5. the chosen walk's node computes theta-hat (Eq. 1) and runs the
     protocol: DECAFORK fork / DECAFORK+ fork-or-terminate /
     MISSINGPERSON timeout replacement;
  6. forks/terminations execute through the slot machinery.

Each stage is traced under a ``jax.named_scope`` on every round path
(the unfused oracle, the fused CPU reference and the TPU whole-round
branch): ``round.topology`` (1), ``round.move`` (2), ``round.threats``
(3), ``round.observe`` (4), ``round.decide`` (5), ``round.fork`` (6) and,
on TPU, ``round.kernel`` for the whole-round Pallas call and the
operands built only for it; the payload hooks run under ``payload.*``.
The scopes are HLO metadata only: they name device ops in a profiler
trace and change no output bit.

The whole trajectory runs under one ``lax.scan``; the live topology is
part of the scan carry, so downed nodes/links persist and recover across
steps. Configs are pytrees with *traced numeric leaves* (see
``protocol.py`` / ``failures.py``) — the topology knobs included — so one
trajectory core batches outward over seeds (vmap) and over (scenario,
seed) stacks, provided the scenarios share static structure (same
algorithm, estimator_impl, max_walks, rt_bins, burst + node-crash
schedule lengths).

This module is the *backend*: the un-jitted cores (``_run_core`` /
``_run_ensemble_core`` / ``_sweep_core``) that ``repro.api.Plan``
compiles through its process-wide signature-keyed executable cache. The
public, declarative surface is ``repro.api.Experiment`` (spec ->
``plan()`` -> results); the four historical runners
(``run_simulation`` / ``run_ensemble`` / ``run_sweep`` and
``repro.sweep.run_scenarios``) remain as deprecation shims that build
the equivalent Experiment, so they stay bitwise-equal to the new path.

Every core accepts a ``payload`` (``core.payload.Payload``): the
computational task the walks carry (flagship: RW-SGD learning via
``optim.rw_sgd.RwSgdPayload``). The payload's carry pytree rides the same
``lax.scan`` — its hooks run inside the compiled trajectory, so learning
curves batch across seeds and scenarios exactly like ``Z_t`` curves, and
the runners additionally return the stacked per-round payload outputs.
``payload=None`` (the default) traces the hook-free program and is
bitwise identical to the pre-payload engine; payload PRNG streams are
disjoint from the simulator's, so even an attached payload leaves every
``StepOutputs`` trajectory bitwise unchanged.

Output selection is static (``core.outputs``): an ``OutputSpec`` picks
which ``StepOutputs`` fields the trajectory scan stacks over time —
scalars-only by default (the per-walk ``(W,)`` fields are auto-recorded
only when a payload is attached) — and a ``PayloadOutputSpec`` does the
same for the payload's per-round outputs, so dropped ``(..., steps, W)``
buffers are never allocated on either side.

The static ``Graph`` stays a trace-time constant (the superset topology);
``GraphState`` only masks it, so scenario rows vary *which parts are up
when* without recompilation. With every topology knob disabled the masks
stay full and each round is bitwise the static-graph round. On the fused
estimator path the observation state (``last_seen``, return-time
histograms) is carried pre-padded to the round kernel's node tile
(``observation_rows``) and sliced back once per run — bitwise-identical
to the per-round pad+slice it replaces.
"""
from __future__ import annotations

from typing import NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core import estimator as est
from repro.core import failures as flr
from repro.core import protocol as prt
from repro.core import walkers as wlk
from repro.core.outputs import SCALARS, StepOutputs
from repro.core.payload import PAYLOAD_STREAM, payload_init_key
from repro.graphs.generators import Graph
from repro.graphs.spectral import stationary_distribution
from repro.graphs.state import (
    GraphState,
    availability,
    availability_rows,
    init_graph_state,
    mirror_indices,
)
from repro.utils.prng import fold_in_time


class SimState(NamedTuple):
    t: jax.Array  # scalar int32
    walks: wlk.WalkState
    last_seen: jax.Array  # (n, W) int32
    # ReturnTimeState (histogram carry) on the unfused / kernel paths,
    # CumulativeReturnState (incremental CDF carry) on the fused-ref
    # whole-round path — decided statically by the config (_will_fuse_round)
    rts: est.ReturnTimeState | est.CumulativeReturnState
    byz_state: jax.Array  # scalar bool
    key: jax.Array
    theta_hist: jax.Array  # (n, TB) warmup theta-hat histogram (auto_eps)
    graph: GraphState  # live topology masks (node_up, edge_up)
    # (1+K,) mobile Pac-Man positions when fcfg.pacman_mobile (a static
    # field, so the carry structure is a trace-time constant); None — an
    # empty pytree subtree — otherwise, leaving the default program's
    # scan carry structurally unchanged
    pacman_pos: jax.Array | None = None


def init_state(
    n: int,
    max_deg: int,
    pcfg: prt.ProtocolConfig,
    fcfg: flr.FailureConfig,
    key: jax.Array,
    n_obs: int | None = None,
    steps: int | None = None,
) -> SimState:
    """Initial simulator state; ``n_obs`` (>= n, default n) is the row
    count of the observation-state arrays (``last_seen``, return-time
    histograms). The fused estimator path carries them PRE-padded to the
    node tile (``observation_rows``) so the per-round pad+slice inside
    the scan disappears; pad rows are masked "no data" rows no walk can
    hit, so every real row is bitwise what the unpadded run computes.

    ``steps`` (static, optional) is the run's step budget: on the
    fused-ref whole-round path the return-time carry is the cumulative
    table trimmed to ``min(rt_bins, steps)`` bins (the same trim
    ``theta_hat_rows`` applies through ``max_elapsed`` — bitwise-neutral,
    see its docstring); without it the carry keeps all ``rt_bins``."""
    n_obs = n if n_obs is None else n_obs
    W = pcfg.max_walks
    k_init, k_run = jax.random.split(key)
    walks = wlk.init_walks(pcfg.z0, W, n, k_init)
    if pcfg.walk_variant != "uniform":
        # function-level import: the zoo package loads only when a
        # non-default variant actually runs (no import cycle either way)
        from repro.zoo.variants import init_variant_state

        walks = init_variant_state(walks, pcfg)
    if pcfg.algorithm == "missingperson":
        if n_obs != n:
            raise ValueError("missingperson does not pad observation state")
        # paper: L_{i,l}(0) = 0 for all initial ids at every node
        last_seen = jnp.where(
            jnp.arange(W)[None, :] < pcfg.z0,
            jnp.zeros((n, W), jnp.int32),
            est.NEVER,
        )
    else:
        last_seen = jnp.full((n_obs, W), est.NEVER, jnp.int32)
        # the starting node of each initial walk has seen it at t=0
        last_seen = last_seen.at[walks.pos, jnp.arange(W)].max(
            jnp.where(walks.active, 0, est.NEVER)
        )
    tb = _theta_bins(pcfg)
    if _will_fuse_round(pcfg, fcfg) and _fused_round_backend() == "ref":
        cbins = pcfg.rt_bins if steps is None else min(
            pcfg.rt_bins, max(int(steps), 1)
        )
        rts = est.init_cumulative_state(n_obs, cbins)
    else:
        rts = est.init_return_time_state(n_obs, pcfg.rt_bins)
    return SimState(
        t=jnp.int32(0),
        walks=walks,
        last_seen=last_seen,
        rts=rts,
        byz_state=jnp.asarray(fcfg.byz_start),
        key=k_run,
        theta_hist=jnp.zeros((n, tb), jnp.float32),
        graph=init_graph_state(n, max_deg),
        pacman_pos=(
            flr.initial_pacman_positions(fcfg) if fcfg.pacman_mobile else None
        ),
    )


def resolved_estimator_impl(pcfg: prt.ProtocolConfig) -> str:
    """``estimator_impl`` with ``'auto'`` resolved for the current
    backend (trace-time; fused on TPU, gather elsewhere)."""
    impl = pcfg.estimator_impl
    if impl == "auto":
        # function-level import: the kernels package (and with it
        # jax.experimental.pallas) loads only when a round actually asks
        from repro.kernels.platform import best_estimator_impl

        impl = best_estimator_impl()
    return impl


def _will_fuse(pcfg: prt.ProtocolConfig) -> bool:
    """Whether the trajectory will take the fused observation path —
    THE fuse predicate (``protocol_step`` consumes it directly, adding
    only its caller-supplied ``pi is None`` guard)."""
    return (
        resolved_estimator_impl(pcfg) == "fused"
        and pcfg.algorithm in ("decafork", "decafork+")
        and not pcfg.analytic_survival
    )


def resolved_round_impl(pcfg: prt.ProtocolConfig) -> str:
    """``round_impl`` with ``'auto'`` resolved for the current backend
    (trace-time; honors the ``REPRO_ROUND_IMPL`` env override)."""
    impl = pcfg.round_impl
    if impl == "auto":
        from repro.kernels.platform import best_round_impl

        impl = best_round_impl()
    return impl


def _fused_round_backend() -> str:
    from repro.kernels.platform import fused_round_backend

    return fused_round_backend()


class RoundDecision(NamedTuple):
    """Trace-time record of how one scenario's round will execute.

    ``impl`` is ``'fused'`` or ``'unfused'``; ``backend`` names the fused
    round flavor (``'ref'``/``'pallas'``) when fused, else None; and
    ``reason`` says WHY — which gate sent an intended-fused config back
    to the stage sequence. ``Plan.round_decisions()`` surfaces this per
    compile group, so a silently-degraded config is one call away from
    explaining itself.
    """

    impl: str
    backend: str | None
    reason: str

    @property
    def fused(self) -> bool:
        return self.impl == "fused"


def round_impl_decision(
    pcfg: prt.ProtocolConfig, fcfg: flr.FailureConfig | None = None
) -> RoundDecision:
    """Resolve how a (protocol, failure) config pair executes its rounds —
    THE whole-round fuse predicate, with the fallback reason attached.
    ``init_state`` (carry representation) and ``protocol_step``
    (dispatch) both consume it, so the carry and the step function agree
    by construction for every caller.

    Gated to the configurations the fused round reproduces bitwise:
    DECAFORK/DECAFORK+ with empirical survival and fixed thresholds, on
    the estimator family the backend's fused round computes — the
    gather family for the ref (incremental-CDF) round, the node-sum
    family (compare/pallas/fused) for the whole-round Pallas kernel.
    Zoo configs narrow this further: non-uniform walk variants always
    take the stage sequence, and the Pallas whole-round kernel (unlike
    the ref round, which shares the jnp failure helpers) does not fuse
    multi/mobile Pac-Man or scheduled edge cuts. Everything else keeps
    the literal unfused sequence, which doubles as the fused path's
    golden oracle (``round_impl="unfused"``).

    ``fcfg=None`` means "no zoo attack statics" (the pre-zoo call shape).
    """

    def unfused(reason: str) -> RoundDecision:
        return RoundDecision("unfused", None, reason)

    impl = resolved_round_impl(pcfg)
    if impl != "fused":
        return unfused(f"round_impl resolved to {impl!r}")
    if pcfg.algorithm not in ("decafork", "decafork+"):
        return unfused(f"algorithm {pcfg.algorithm!r} has no fused round")
    if pcfg.analytic_survival:
        return unfused("analytic_survival only runs the stage sequence")
    if pcfg.auto_eps:
        return unfused("auto_eps thresholds only run the stage sequence")
    eimpl = resolved_estimator_impl(pcfg)
    backend = _fused_round_backend()
    if backend == "pallas":
        if eimpl not in ("compare", "pallas", "fused"):
            return unfused(
                f"estimator_impl {eimpl!r} is outside the pallas fused "
                "round's node-sum family"
            )
    elif eimpl != "gather":
        return unfused(
            f"estimator_impl {eimpl!r} is outside the ref fused round's "
            "gather family"
        )
    if pcfg.walk_variant != "uniform":
        return unfused(
            f"walk_variant {pcfg.walk_variant!r} has no fused round"
        )
    if fcfg is not None and backend == "pallas":
        if fcfg.pacman_mobile:
            return unfused(
                "mobile Pac-Man is not in the pallas whole-round kernel"
            )
        if fcfg.n_pacman:
            return unfused(
                "multiple Pac-Man nodes are not in the pallas whole-round "
                "kernel"
            )
        if fcfg.n_edge_cuts:
            return unfused(
                "scheduled edge cuts are not in the pallas whole-round "
                "kernel"
            )
    return RoundDecision(
        "fused", backend, f"all stages supported by the {backend} fused round"
    )


def _will_fuse_round(
    pcfg: prt.ProtocolConfig, fcfg: flr.FailureConfig | None = None
) -> bool:
    """Boolean view of :func:`round_impl_decision` (see its docstring)."""
    return round_impl_decision(pcfg, fcfg).fused


def observation_rows(
    n: int,
    pcfg: prt.ProtocolConfig,
    fcfg: flr.FailureConfig | None = None,
) -> int:
    """Static row count of the observation-state arrays for a run.

    On the fused paths (observation-fused estimator, or the whole-round
    Pallas kernel) the node axis is padded up to the round kernel's
    tile ONCE here, instead of pad+slice every round inside the scan (one
    observation-state copy per round saved whenever ``n`` is not
    tile-aligned); everywhere else it is just ``n``.
    """
    pad_for_kernel = _will_fuse(pcfg) or (
        _will_fuse_round(pcfg, fcfg) and _fused_round_backend() == "pallas"
    )
    if not pad_for_kernel:
        return n
    from repro.kernels.round_update import DEFAULT_BLOCK_NODES

    bn = min(DEFAULT_BLOCK_NODES, n)
    return n + (-n) % bn


def _theta_bins(pcfg: prt.ProtocolConfig) -> int:
    # theta-hat <= 0.5 + (slots - 1); one extra bin absorbs the tail
    return int((pcfg.max_walks + 1) / pcfg.theta_bin_width) + 1


def protocol_step(
    state: SimState,
    pcfg: prt.ProtocolConfig,
    fcfg: flr.FailureConfig,
    neighbors: jax.Array,
    degrees: jax.Array,
    mirror: jax.Array,
    pi: jax.Array | None,
    *,
    max_elapsed: int | None = None,
):
    """One synchronous round; returns (next state, per-step outputs).

    ``max_elapsed`` (static) is an optional upper bound on ``t`` over the
    whole run — the trajectory scan passes its ``steps`` — letting the
    estimator trim the dead tail of the cumulative return-time table
    (bitwise-identical results; see ``estimator.theta_hat_rows``).

    When ``_will_fuse_round(pcfg)`` holds, the round dispatches to the
    fused whole-round implementation (``_protocol_step_fused``) — bitwise
    the sequence below, verified by the whole-round golden tests. This
    function body IS the unfused oracle (``round_impl="unfused"``).
    """
    if _will_fuse_round(pcfg, fcfg):
        if pi is not None:
            raise ValueError(
                "the fused whole-round path does not take an analytic-"
                "survival table; pass round_impl='unfused' (or a config "
                "with analytic_survival=True, which never fuses)"
            )
        return _protocol_step_fused(state, pcfg, fcfg, neighbors, degrees, mirror)
    t = state.t
    key = state.key
    k_move = fold_in_time(key, t, 0)
    k_pfail = fold_in_time(key, t, 1)
    k_burst = fold_in_time(key, t, 2)
    k_byz = fold_in_time(key, t, 3)
    k_dec = fold_in_time(key, t, 4)
    k_topo = fold_in_time(key, t, 5)

    ws = state.walks
    n_before = jnp.sum(ws.active)

    # 1. topology evolves; a crashing node kills its resident walks
    with jax.named_scope("round.topology"):
        gs = flr.step_topology(state.graph, t, fcfg, k_topo, neighbors, mirror)
        ws = ws._replace(
            active=flr.kill_resident_walks(ws.active, ws.pos, gs.node_up)
        )

        # 1b. a mobile Pac-Man hops over the same live topology the walks
        # see (dedicated stream tag 6 + 1: never perturbs the walk/decision
        # draws)
        pac_pos = state.pacman_pos
        if fcfg.pacman_mobile:
            k_pac = fold_in_time(key, t, 7)
            pac_pos = flr.step_mobile_pacman(
                pac_pos, t, fcfg, k_pac, neighbors, degrees,
                availability(gs, neighbors, degrees),
            )

    # 2. movement over the currently-available edges; non-uniform zoo
    # variants (jump / biased / bloom) are whole other static programs
    with jax.named_scope("round.move"):
        if pcfg.walk_variant == "uniform":
            ws = wlk.move_walks(
                ws, neighbors, degrees, k_move,
                availability(gs, neighbors, degrees),
            )
        else:
            from repro.zoo.variants import move_variant

            ws = move_variant(
                ws, pcfg, neighbors, degrees, k_move,
                availability(gs, neighbors, degrees), gs.node_up,
            )

    # 3. walk-level threat models
    with jax.named_scope("round.threats"):
        active = flr.apply_probabilistic_failures(ws.active, t, fcfg, k_pfail)
        active = flr.apply_burst_failures(active, t, fcfg, k_burst)
        active, byz_state = flr.step_byzantine(
            active, ws.pos, t, state.byz_state, fcfg, k_byz
        )
        active = flr.apply_pacman(active, ws.pos, t, fcfg, pac_pos)
        ws = ws._replace(active=active)
        n_failed = n_before - jnp.sum(active)

    # 4. observations: return samples + last-seen updates for ALL visitors
    impl = resolved_estimator_impl(pcfg)
    # `pi is None` guards direct callers that pass an analytic-survival
    # table independently of pcfg; the padding decision (_will_fuse,
    # observation_rows) must stay a superset-consistent view of this.
    fuse = _will_fuse(pcfg) and pi is None
    with jax.named_scope("round.observe"):
        last_seen = state.last_seen
        prev = last_seen[ws.pos, ws.track]  # (W,)
        r = t - prev
        valid = ws.active & (prev != est.NEVER) & (r >= 1)
        upd = jnp.where(ws.active, t, est.NEVER)
        node_sums = None
        if fuse:
            # one fused pass: scatter + max-update + node theta-sums
            # (kernels/round_update.py; Pallas tiles on TPU, jnp elsewhere)
            from repro.kernels.round_update import round_update

            last_seen, hist, tot, node_sums = round_update(
                last_seen, state.rts.hist, state.rts.total,
                ws.pos, ws.track, r, valid, upd, t,
            )
            rts = est.ReturnTimeState(hist=hist, total=tot)
        else:
            rts = est.record_returns(state.rts, ws.pos, r, valid)
            last_seen = last_seen.at[ws.pos, ws.track].max(upd, mode="drop")

    # 5. estimation + decisions for chosen walks; 6. forks/terminations
    enabled = t >= pcfg.protocol_start
    theta_hist = state.theta_hist
    if pcfg.algorithm in ("decafork", "decafork+"):
        with jax.named_scope("round.decide"):
            chosen = prt.choose_walks(ws.pos, ws.active, degrees.shape[0])
            if fuse:
                theta = est.theta_hat_from_node_sums(node_sums, ws.pos)
            elif impl == "gather" or pi is not None:
                theta = est.theta_hat_rows(
                    last_seen, rts.hist, rts.total, t, ws.pos, ws.track,
                    pi=pi, max_elapsed=max_elapsed,
                )
            elif impl == "compare":
                sums = est.node_sums_compare(last_seen, rts.hist, rts.total, t)
                theta = est.theta_hat_from_node_sums(sums, ws.pos)
            elif impl == "pallas":
                from repro.kernels import theta_sums_pallas

                sums = theta_sums_pallas(last_seen, rts.hist, rts.total, t)
                theta = est.theta_hat_from_node_sums(sums, ws.pos)
            else:
                raise ValueError(impl)
            # beyond-paper: per-node self-calibrated thresholds (auto_eps)
            if pcfg.auto_eps:
                warmup = ~enabled
                b = jnp.clip(
                    (theta / pcfg.theta_bin_width).astype(jnp.int32),
                    0,
                    theta_hist.shape[1] - 1,
                )
                w = (chosen & warmup).astype(jnp.float32)
                theta_hist = theta_hist.at[ws.pos, b].add(w, mode="drop")
                eps_w, eps2_w = prt.theta_quantile_thresholds(
                    theta_hist, ws.pos, pcfg
                )
                fork_mask, term_mask = prt.decafork_decisions(
                    theta, chosen, k_dec, pcfg, enabled, eps=eps_w, eps2=eps2_w
                )
            else:
                fork_mask, term_mask = prt.decafork_decisions(
                    theta, chosen, k_dec, pcfg, enabled
                )
        with jax.named_scope("round.fork"):
            ws = wlk.execute_terminations(ws, term_mask)
            n_terms = jnp.sum(term_mask)
            ws, last_seen, n_forks, fork_parent = wlk.execute_forks(
                ws, last_seen, fork_mask, ws.pos, None, t
            )
        theta_mean = jnp.sum(jnp.where(chosen, theta, 0.0)) / jnp.maximum(
            jnp.sum(chosen), 1
        )
    elif pcfg.algorithm == "missingperson":
        with jax.named_scope("round.decide"):
            chosen = prt.choose_walks(ws.pos, ws.active, degrees.shape[0])
            ev = prt.missingperson_decisions(
                last_seen, ws.pos, ws.track, chosen, t, k_dec, pcfg, enabled
            )  # (W, C) — only initial-id columns (< z0) can fire
        with jax.named_scope("round.fork"):
            ws, last_seen, n_forks, fork_parent = wlk.execute_grid_forks(
                ws, last_seen, ev
            )
        n_terms = jnp.int32(0)
        term_mask = jnp.zeros((ev.shape[0],), bool)
        theta_mean = jnp.float32(0.0)
    else:  # 'none': plain multi-RW system without self-regulation
        n_forks = jnp.int32(0)
        n_terms = jnp.int32(0)
        theta_mean = jnp.float32(0.0)
        fork_parent = jnp.full((ws.pos.shape[0],), -1, jnp.int32)
        term_mask = jnp.zeros_like(ws.active)

    new_state = SimState(
        t=t + 1,
        walks=ws,
        last_seen=last_seen,
        rts=rts,
        byz_state=byz_state,
        key=key,
        theta_hist=theta_hist,
        graph=gs,
        pacman_pos=pac_pos,
    )
    out = StepOutputs(
        z=jnp.sum(ws.active),
        forks=n_forks,
        terms=n_terms,
        failures=n_failed,
        theta_mean=theta_mean,
        fork_parent=fork_parent,
        terminated=term_mask,
    )
    return new_state, out


def _protocol_step_fused(
    state: SimState,
    pcfg: prt.ProtocolConfig,
    fcfg: flr.FailureConfig,
    neighbors: jax.Array,
    degrees: jax.Array,
    mirror: jax.Array,
):
    """The fused whole-round implementation behind ``round_impl="fused"``.

    Bitwise-identical to the unfused sequence in ``protocol_step`` (its
    golden oracle) by construction: every PRNG stream is derived with the
    exact same key folds, the failure/topology helpers are the same
    functions, and each restructured stage is an exact-arithmetic
    transform of its unfused counterpart —

      * movement is row-restricted (``move_walks_rows`` over
        ``availability_rows`` at the walks' own rows) — the rank-select
        acts row-locally, so gathering first changes nothing;
      * "choose one walk per node" is the (W, W) pairwise minimum
        (``choose_walks_pairwise``) instead of an (n,)-scatter;
      * on the ref backend (CPU/GPU) the return-time statistics are the
        incrementally-carried cumulative table
        (``CumulativeReturnState``): observation is a scatter-add of 0/1
        step rows and theta reads prefix counts straight off the carry
        (``theta_hat_cumulative``) — no per-round cumsum, which XLA CPU
        lowers to a quadratic reduce-window and which dominated the
        PR-4 round;
      * on TPU the whole round (hop + topology + failures + observation
        + decisions) is one node-tiled Pallas pass
        (``kernels.round_update.whole_round_pallas``) with all uniforms
        pre-drawn from the same streams.

    Fork/terminate execution (slot machinery) stays outside in both
    branches — it is walk-sized and shared with every other path.
    """
    t = state.t
    key = state.key
    k_move = fold_in_time(key, t, 0)
    k_pfail = fold_in_time(key, t, 1)
    k_burst = fold_in_time(key, t, 2)
    k_byz = fold_in_time(key, t, 3)
    k_dec = fold_in_time(key, t, 4)
    k_topo = fold_in_time(key, t, 5)

    ws = state.walks
    W = ws.pos.shape[0]
    n = degrees.shape[0]
    n_before = jnp.sum(ws.active)
    enabled = t >= pcfg.protocol_start
    pac_pos = state.pacman_pos

    if _fused_round_backend() == "ref":
        # 1. topology evolves; a crashing node kills its resident walks
        # (step_topology already applies any scheduled edge cuts)
        with jax.named_scope("round.topology"):
            gs = flr.step_topology(
                state.graph, t, fcfg, k_topo, neighbors, mirror
            )
            ws = ws._replace(
                active=flr.kill_resident_walks(ws.active, ws.pos, gs.node_up)
            )

            # 1b. mobile Pac-Man hop — same helper, same dedicated stream
            # as the unfused sequence, so the positions stay its exact bits
            if fcfg.pacman_mobile:
                k_pac = fold_in_time(key, t, 7)
                pac_pos = flr.step_mobile_pacman(
                    pac_pos, t, fcfg, k_pac, neighbors, degrees,
                    availability(gs, neighbors, degrees),
                )

        # 2. movement, row-restricted to the walks' own adjacency rows
        with jax.named_scope("round.move"):
            u_move = jax.random.uniform(k_move, (W,))
            avail_rows = availability_rows(
                gs.edge_up[ws.pos], gs.node_up[ws.pos], gs.node_up,
                neighbors[ws.pos], degrees[ws.pos],
            )
            ws = ws._replace(
                pos=wlk.move_walks_rows(
                    ws, neighbors[ws.pos], u_move, avail_rows, degrees.dtype
                )
            )

        # 3. walk-level threat models (same helpers, same keys)
        with jax.named_scope("round.threats"):
            active = flr.apply_probabilistic_failures(
                ws.active, t, fcfg, k_pfail
            )
            active = flr.apply_burst_failures(active, t, fcfg, k_burst)
            active, byz_state = flr.step_byzantine(
                active, ws.pos, t, state.byz_state, fcfg, k_byz
            )
            active = flr.apply_pacman(active, ws.pos, t, fcfg, pac_pos)
            ws = ws._replace(active=active)
            n_failed = n_before - jnp.sum(active)

        # 4. observations on the incremental cumulative carry
        with jax.named_scope("round.observe"):
            last_seen = state.last_seen
            prev = last_seen[ws.pos, ws.track]
            r = t - prev
            valid = ws.active & (prev != est.NEVER) & (r >= 1)
            upd = jnp.where(ws.active, t, est.NEVER)
            rts = est.record_returns_cumulative(
                state.rts, ws.pos, r, valid, pcfg.rt_bins
            )
            last_seen = last_seen.at[ws.pos, ws.track].max(upd, mode="drop")

        # 5. estimation + decisions; no cumsum anywhere
        with jax.named_scope("round.decide"):
            chosen = prt.choose_walks_pairwise(ws.pos, ws.active)
            theta = est.theta_hat_cumulative(
                last_seen, rts, t, ws.pos, ws.track
            )
            fork_mask, term_mask = prt.decafork_decisions(
                theta, chosen, k_dec, pcfg, enabled
            )
    else:
        # TPU: one whole-round Pallas pass; pre-draw every uniform from
        # the exact streams the unfused sequence consumes, each under the
        # stage that consumes it
        from repro.kernels.round_update import whole_round_pallas

        n_obs = state.last_seen.shape[0]
        K = fcfg.n_bursts

        # pad rows stay down forever: node_up False, recovery uniform 1.0
        def _pad_nodes(x, fill):
            pad = n_obs - x.shape[0]
            if pad == 0:
                return x
            return jnp.concatenate(
                [x, jnp.full((pad,) + x.shape[1:], fill, x.dtype)]
            )

        with jax.named_scope("round.move"):
            u_move = jax.random.uniform(k_move, (W,))
        with jax.named_scope("round.threats"):
            u_pfail = jax.random.uniform(k_pfail, (W,))
            if K:
                u_burst = jnp.stack(
                    [
                        jax.random.uniform(jax.random.fold_in(k_burst, i), (W,))
                        for i in range(K)
                    ]
                )
                burst_sizes_eff = jnp.stack(
                    [
                        jnp.where(
                            t == fcfg.burst_times[i], fcfg.burst_sizes[i], 0
                        )
                        for i in range(K)
                    ]
                ).astype(jnp.int32)
            else:
                u_burst = jnp.ones((1, W), jnp.float32)
                burst_sizes_eff = jnp.zeros((1,), jnp.int32)
        with jax.named_scope("round.decide"):
            k_fork, k_term = jax.random.split(k_dec)
            u_fork = jax.random.uniform(k_fork, (W,))
            u_term = jax.random.uniform(k_term, (W,))
        with jax.named_scope("round.topology"):
            u_nfail, u_nrec, e_fail, e_rec = flr.topology_uniforms(
                k_topo, neighbors, mirror
            )
            sched_down = flr.scheduled_crash_mask(n, t, fcfg)
            topo_pads = (
                _pad_nodes(u_nfail, 1.0), _pad_nodes(u_nrec, 1.0),
                _pad_nodes(sched_down, False),
                _pad_nodes(e_fail, 1.0), _pad_nodes(e_rec, 1.0),
            )

        # Byzantine chain advances outside (one scalar draw); the kernel
        # only needs "which node kills this round" (-1: none)
        with jax.named_scope("round.threats"):
            byz_armed = (t >= fcfg.byz_start_time) & (fcfg.byzantine_node >= 0)
            flip = (jax.random.uniform(k_byz, ()) < fcfg.p_byz) & byz_armed
            byz_state = jnp.logical_xor(state.byz_state, flip)
            byz_kill_node = jnp.where(
                byz_state & byz_armed, fcfg.byzantine_node, -1
            ).astype(jnp.int32)
            pac_armed = (
                (t >= fcfg.pacman_start_time) & (fcfg.pacman_node >= 0)
            )
            pac_node = jnp.where(pac_armed, fcfg.pacman_node, -1).astype(
                jnp.int32
            )

        with jax.named_scope("round.kernel"):
            # start-gated rates fold the gate into the threshold (u in
            # [0,1) is never < -1, so "not started" == rate -1)
            p_fail_eff = jnp.where(t >= fcfg.p_fail_start, fcfg.p_fail, -1.0)
            p_nf_eff = jnp.where(
                t >= fcfg.node_fail_start, fcfg.p_node_fail, -1.0
            )
            p_lf_eff = jnp.where(
                t >= fcfg.link_fail_start, fcfg.p_link_fail, -1.0
            )
            outs = whole_round_pallas(
                state.last_seen, state.rts.hist, state.rts.total,
                _pad_nodes(state.graph.node_up, False),
                _pad_nodes(state.graph.edge_up, False),
                ws.pos, ws.track, ws.active,
                neighbors[ws.pos], degrees[ws.pos],
                state.graph.edge_up[ws.pos], e_fail[ws.pos], e_rec[ws.pos],
                u_move, u_pfail, u_fork, u_term,
                u_burst, burst_sizes_eff,
                *topo_pads,
                params_f=jnp.stack(
                    [
                        jnp.asarray(p_fail_eff, jnp.float32),
                        jnp.asarray(p_nf_eff, jnp.float32),
                        jnp.asarray(p_lf_eff, jnp.float32),
                        jnp.asarray(fcfg.p_node_recover, jnp.float32),
                        jnp.asarray(fcfg.p_link_recover, jnp.float32),
                        jnp.asarray(pcfg.eps, jnp.float32),
                        jnp.asarray(pcfg.eps2, jnp.float32),
                        jnp.asarray(pcfg.p, jnp.float32),
                    ]
                )[None, :],
                params_i=jnp.stack(
                    [
                        jnp.asarray(t, jnp.int32),
                        byz_kill_node,
                        pac_node,
                        enabled.astype(jnp.int32),
                    ]
                )[None, :],
                decafork_plus=pcfg.algorithm == "decafork+",
            )
        (last_seen, hist, tot, node_up_new, edge_up_new,
         pos_new, act_new, theta, chosen, fork_mask, term_mask) = outs
        gs = GraphState(node_up=node_up_new[:n], edge_up=edge_up_new[:n])
        ws = ws._replace(pos=pos_new, active=act_new)
        rts = est.ReturnTimeState(hist=hist, total=tot)
        n_failed = n_before - jnp.sum(act_new)

    # forks/terminations execute through the shared slot machinery
    with jax.named_scope("round.fork"):
        ws = wlk.execute_terminations(ws, term_mask)
        n_terms = jnp.sum(term_mask)
        ws, last_seen, n_forks, fork_parent = wlk.execute_forks(
            ws, last_seen, fork_mask, ws.pos, None, t
        )
    theta_mean = jnp.sum(jnp.where(chosen, theta, 0.0)) / jnp.maximum(
        jnp.sum(chosen), 1
    )

    new_state = SimState(
        t=t + 1,
        walks=ws,
        last_seen=last_seen,
        rts=rts,
        byz_state=byz_state,
        key=key,
        theta_hist=state.theta_hist,
        graph=gs,
        pacman_pos=pac_pos,
    )
    out = StepOutputs(
        z=jnp.sum(ws.active),
        forks=n_forks,
        terms=n_terms,
        failures=n_failed,
        theta_mean=theta_mean,
        fork_parent=fork_parent,
        terminated=term_mask,
    )
    return new_state, out


def _strip_obs_pad(state: SimState, n: int, pcfg: prt.ProtocolConfig) -> SimState:
    """Final-state normalization: slice the pre-padded observation rows
    back to the graph's ``n`` (one slice per *run*, vs one pad+slice per
    round without carrying padded state) and convert a cumulative
    whole-round carry back to the public ``ReturnTimeState`` (exact
    integer transform — see ``estimator.cumulative_to_return_time``), so
    every consumer of a final state sees one representation."""
    rts = state.rts
    if isinstance(rts, est.CumulativeReturnState):
        rts = est.cumulative_to_return_time(rts, pcfg.rt_bins)
        state = state._replace(rts=rts)
    if state.last_seen.shape[0] == n:
        return state
    return state._replace(
        last_seen=state.last_seen[:n],
        rts=est.ReturnTimeState(
            hist=state.rts.hist[:n], total=state.rts.total[:n]
        ),
    )


def _init_carry(key, neighbors, pcfg, fcfg, steps, n, payload=None):
    """The trajectory's step-0 carry: ``(SimState, payload carry | None)``.

    This is the SAME initialization ``_run_core`` performs (same key
    splits, same observation-row padding, same cumulative-carry trim on
    ``steps`` — the TOTAL step budget, never a segment length), factored
    out so the segmented execution path starts from bitwise the state
    the monolithic scan starts from.
    """
    n_obs = observation_rows(n, pcfg, fcfg)
    state = init_state(
        n, neighbors.shape[1], pcfg, fcfg, key, n_obs=n_obs, steps=steps
    )
    pcarry = payload.init(payload_init_key(key)) if payload is not None else None
    return (state, pcarry)


def _scan_chunk(
    carry, neighbors, degrees, mirror, pi, pcfg, fcfg, length, steps,
    payload=None, spec=SCALARS, pspec=None,
):
    """Advance a trajectory carry by ``length`` rounds — THE scan body.

    ``_run_core`` calls this once with ``length == steps``; the segment
    cores call it per segment. Both trace the identical per-round body
    (``protocol_step`` + payload hooks), and every PRNG stream folds the
    carried step counter ``state.t`` — never the loop index — so where
    the scan is *split* cannot change a single drawn bit. ``steps`` (the
    total budget) feeds ``max_elapsed`` so the estimator's bin trim is a
    whole-run constant.

    With ``payload=None`` the scan carry is the bare ``SimState``
    (exactly the pre-segmentation program); with a payload it is
    ``(SimState, payload_carry)`` and each round runs the hook sequence
    ``on_terminate -> on_fork -> on_visit`` after the protocol round,
    mirroring the protocol's own order (``execute_terminations`` frees
    slots *before* ``execute_forks`` reallocates them, so a slot can be
    terminated and re-forked in one round — clearing must not clobber the
    fresh copy); the forked walk trains at its origin node the very round
    it is created, on a copy of its parent's pre-round replica.
    """
    state, pcarry = carry

    if payload is None:

        def body(s, _):
            s2, out = protocol_step(
                s, pcfg, fcfg, neighbors, degrees, mirror, pi,
                max_elapsed=steps,
            )
            return s2, spec.select(out)

        final, recorded = jax.lax.scan(body, state, None, length=length)
        return (final, None), recorded

    def body(c, _):
        s, pc = c
        t = s.t  # pre-round step counter, matching the simulator's streams
        k_visit = fold_in_time(s.key, t, PAYLOAD_STREAM)
        s2, out = protocol_step(
            s, pcfg, fcfg, neighbors, degrees, mirror, pi, max_elapsed=steps
        )
        with jax.named_scope("payload.fork"):
            pc = payload.on_terminate(pc, out.terminated)
            pc = payload.on_fork(pc, out.fork_parent)
        pc, pout = payload.on_visit(pc, s2.walks, t, k_visit)
        if pspec is not None:
            pout = pspec.select(pout)
        return (s2, pc), (spec.select(out), pout)

    (final, pcarry), recorded = jax.lax.scan(
        body, (state, pcarry), None, length=length
    )
    return (final, pcarry), recorded


def _run_core(
    key, neighbors, degrees, mirror, pi, pcfg, fcfg, steps, n,
    payload=None, spec=SCALARS, pspec=None,
):
    """Un-jitted single-trajectory scan; every batching wrapper traces
    through this one function so ensemble/sweep results are bitwise equal
    to the single-run path. This is the ONE backend ``repro.api.Plan``
    compiles — the jitted executables live in the Plan's process-wide
    cache, keyed on the static signature.

    ``spec`` (an ``OutputSpec``, static) selects which ``StepOutputs``
    fields the scan stacks over time: the full per-round StepOutputs is
    free *inside* the round, but every recorded field costs a
    ``(steps, ...)`` output buffer — O(W) extra HBM traffic per round for
    the per-walk fields — so the thinned view is the default and the
    dropped stacks are never allocated at all. ``pspec`` (a
    ``PayloadOutputSpec`` or None, static) does the same for the payload's
    per-round outputs; ``None`` records the payload's full output pytree
    untouched.

    On the fused estimator path the observation state is carried
    PRE-padded to the round kernel's node tile (``observation_rows``) and
    sliced back once after the scan — bitwise-identical to padding every
    round, without the per-round state copy.

    The body is :func:`_scan_chunk` with ``length == steps``; the
    durable-execution path (``Plan.*_segmented`` over ``_seg_run_core``)
    runs the same chunks with checkpoint boundaries in between, so the
    two are bitwise-equal by construction (and golden-tested as such).
    Returns ``(final SimState, RecordedOutputs)`` — with a payload,
    ``((final SimState, final carry), (RecordedOutputs, payload_outputs))``.
    """
    carry = _init_carry(key, neighbors, pcfg, fcfg, steps, n, payload)
    (final, pcarry), recorded = _scan_chunk(
        carry, neighbors, degrees, mirror, pi, pcfg, fcfg, steps, steps,
        payload, spec, pspec,
    )
    final = _strip_obs_pad(final, n, pcfg)
    if payload is None:
        return final, recorded
    return (final, pcarry), recorded


# deliberately NO input donation on any entry point: the trajectory
# outputs never alias the (tiny) key/config inputs, and donating a
# caller-owned key would break the standard same-key-different-config
# comparison on accelerators. The memory win that matters — reusing the
# scan carry (last_seen/hist/topology state) in place every round — is
# already done by XLA inside the compiled program.


def _run_ensemble_core(
    keys, neighbors, degrees, mirror, pi, pcfg, fcfg, steps, n,
    payload=None, spec=SCALARS, pspec=None,
):
    """(seeds,) keys -> RecordedOutputs with leading (seeds,) axis (a
    (RecordedOutputs, payload_outputs) pair when a payload is attached)."""
    return jax.vmap(
        lambda k: _run_core(
            k, neighbors, degrees, mirror, pi, pcfg, fcfg, steps, n,
            payload, spec, pspec,
        )[1]
    )(keys)


def _split_scenarios(mesh, fn, stacked, shared):
    """``fn(*stacked, *shared)``, whose ``stacked`` arguments and outputs
    all lead with the scenario axis. With a ``mesh`` (from
    ``Placement.place``) each device runs its block of scenarios under
    ``shard_map`` over the 'data' axis: a Mosaic kernel in the round
    cannot be partitioned automatically, and no scenario needs another's
    data. ``shared`` arguments are replicated."""
    if mesh is None:
        return fn(*stacked, *shared)
    from jax.sharding import PartitionSpec as P

    # no collectives: per-device blocks never mix, so the scan carries'
    # varying-axis types need no checking
    specs = (P("data"),) * len(stacked) + (P(),) * len(shared)
    return jax.shard_map(
        fn, mesh=mesh, in_specs=specs, out_specs=P("data"), check_vma=False
    )(*stacked, *shared)


def _vmap_scenarios(fn, *stacked):
    """``jax.vmap(fn)`` over the leading scenario axis of ``stacked``.

    A one-row stack runs ``fn`` unbatched on its row and re-adds the axis:
    that is the ensemble's own program, so a one-scenario sweep row is
    bitwise its ``ensemble`` even where XLA rounds a batch-of-one matmul
    differently from the unbatched one (it does on CPU for payload
    models)."""
    if jax.tree_util.tree_leaves(stacked)[0].shape[0] == 1:
        row = jax.tree_util.tree_map(lambda x: x[0], stacked)
        return jax.tree_util.tree_map(lambda x: x[None], fn(*row))
    return jax.vmap(fn)(*stacked)


def _sweep_core(
    keys, neighbors, degrees, mirror, pi, pcfgs, fcfgs, steps, n,
    payload=None, spec=SCALARS, pspec=None, mesh=None,
):
    """Stacked configs (leaves with leading (S,) axis) + (seeds,) keys ->
    RecordedOutputs with leading (S, seeds) axes, all in one XLA program
    (a (RecordedOutputs, payload_outputs) pair when a payload is
    attached). ``mesh`` splits the scenario axis (``_split_scenarios``)."""

    def scenarios(pcfgs, fcfgs, keys, neighbors, degrees, mirror, pi):
        def one_scenario(pcfg, fcfg):
            return jax.vmap(
                lambda k: _run_core(
                    k, neighbors, degrees, mirror, pi, pcfg, fcfg, steps, n,
                    payload, spec, pspec,
                )[1]
            )(keys)

        return _vmap_scenarios(one_scenario, pcfgs, fcfgs)

    return _split_scenarios(
        mesh, scenarios, (pcfgs, fcfgs), (keys, neighbors, degrees, mirror, pi)
    )


# ---------------------------------------------------------------------------
# Segmented (durable) execution cores
# ---------------------------------------------------------------------------
#
# A segmented run is the monolithic scan split at host-visible
# boundaries: the carry ``(SimState, payload_carry)`` — the int16
# histogram / cumulative return carry, zoo columns (``prev``/``bloom``),
# mobile Pac-Man positions, live topology masks, payload replicas, all
# of it — crosses each boundary as a plain pytree the host can
# ``checkpoint.save_pytree`` and reload. Because every PRNG stream folds
# the carried step counter (never a loop index), and because each
# segment traces the identical ``_scan_chunk`` body, interrupting at any
# boundary and resuming from the snapshot is BITWISE the uninterrupted
# run (``tests/test_resume.py`` proves it per algorithm x attack). The
# drivers that thread snapshots through these cores live in
# ``repro.api.plan`` (``Plan.run_segmented`` / ``ensemble_segmented`` /
# ``sweep_stacked(segment_steps=...)``).


def _seg_run_core(
    carry, neighbors, degrees, mirror, pi, pcfg, fcfg, seg_len, steps, n,
    payload=None, spec=SCALARS, pspec=None,
):
    """One segment of one trajectory: carry -> (carry', recorded chunk).

    ``seg_len`` (static) is this segment's round count; ``steps`` stays
    the TOTAL budget (it feeds the estimator's bin trim, a whole-run
    constant). ``n`` only shapes the static signature — the final
    ``_strip_obs_pad`` happens once, host-side, after the last segment.
    """
    del n  # signature parity with _run_core; padding strips at the end
    return _scan_chunk(
        carry, neighbors, degrees, mirror, pi, pcfg, fcfg, seg_len, steps,
        payload, spec, pspec,
    )


def _seg_ensemble_core(
    carry, neighbors, degrees, mirror, pi, pcfg, fcfg, seg_len, steps, n,
    payload=None, spec=SCALARS, pspec=None,
):
    """One segment of a seed ensemble (carry leaves lead with (seeds,))."""
    return jax.vmap(
        lambda c: _seg_run_core(
            c, neighbors, degrees, mirror, pi, pcfg, fcfg, seg_len, steps, n,
            payload, spec, pspec,
        )
    )(carry)


def _seg_sweep_core(
    carry, neighbors, degrees, mirror, pi, pcfgs, fcfgs, seg_len, steps, n,
    payload=None, spec=SCALARS, pspec=None, mesh=None,
):
    """One segment of a stacked sweep (carry leaves lead with (S, seeds));
    ``mesh`` splits the scenario axis (``_split_scenarios``)."""

    def scenarios(carry, pcfgs, fcfgs, neighbors, degrees, mirror, pi):
        def one_scenario(c, pcfg, fcfg):
            return jax.vmap(
                lambda cc: _seg_run_core(
                    cc, neighbors, degrees, mirror, pi, pcfg, fcfg, seg_len,
                    steps, n, payload, spec, pspec,
                )
            )(c)

        return _vmap_scenarios(one_scenario, carry, pcfgs, fcfgs)

    return _split_scenarios(
        mesh, scenarios, (carry, pcfgs, fcfgs), (neighbors, degrees, mirror, pi)
    )


def _init_ensemble_carry(keys, neighbors, pcfg, fcfg, steps, n, payload=None):
    """Step-0 carries for a seed ensemble: leaves lead with (seeds,)."""
    return jax.vmap(
        lambda k: _init_carry(k, neighbors, pcfg, fcfg, steps, n, payload)
    )(keys)


def _init_sweep_carry(keys, neighbors, pcfgs, fcfgs, steps, n, payload=None):
    """Step-0 carries for a stacked sweep: leaves lead with (S, seeds)."""

    def one_scenario(pcfg, fcfg):
        return jax.vmap(
            lambda k: _init_carry(k, neighbors, pcfg, fcfg, steps, n, payload)
        )(keys)

    return jax.vmap(one_scenario)(pcfgs, fcfgs)


def _finalize_segmented(carry, n, pcfg, payload=None):
    """Host-side final-state normalization after the last segment — the
    exact ``_strip_obs_pad`` the monolithic core applies inside jit."""
    state, pcarry = carry
    state = _strip_obs_pad(state, n, pcfg)
    if payload is None:
        return state
    return (state, pcarry)


def _graph_arrays(graph: Graph, pcfg: prt.ProtocolConfig):
    """The trace-time graph constants one run needs (benchmark baselines
    drive the cores directly through this; the Plan prepares the same
    arrays once per plan instead of once per call)."""
    neighbors = jnp.asarray(graph.neighbors)
    degrees = jnp.asarray(graph.degrees)
    mirror = jnp.asarray(mirror_indices(graph))
    pi = (
        jnp.asarray(stationary_distribution(graph), jnp.float32)
        if pcfg.analytic_survival
        else None
    )
    return neighbors, degrees, mirror, pi


# ---------------------------------------------------------------------------
# Legacy runner shims (deprecated; use repro.api.Experiment)
# ---------------------------------------------------------------------------
#
# The four historical entry points survive as THIN shims over the
# declarative API — they build the equivalent Experiment, lower it to a
# Plan and run it, so they are bitwise-equal to the new path by
# construction (and golden-tested as such). No in-repo code may call
# them; the test lanes promote APIDeprecationWarning to an error.


def run_simulation(
    graph: Graph,
    pcfg: prt.ProtocolConfig,
    fcfg: flr.FailureConfig,
    steps: int,
    key: jax.Array | int = 0,
    *,
    payload=None,
    outputs=None,
):
    """DEPRECATED shim: one trajectory.

    Use ``repro.api.Experiment(graph=..., protocol=pcfg, failures=fcfg,
    steps=steps, ...).run(key)`` — same return value, same bits.
    """
    from repro.api import Experiment
    from repro.utils.deprecation import warn_legacy_runner

    warn_legacy_runner(
        "repro.core.run_simulation", "Experiment(...).run(key)"
    )
    return Experiment(
        graph=graph, protocol=pcfg, failures=fcfg, steps=steps,
        payload=payload, outputs=outputs,
    ).run(key)


def run_ensemble(
    graph: Graph,
    pcfg: prt.ProtocolConfig,
    fcfg: flr.FailureConfig,
    steps: int,
    seeds: int,
    base_key: jax.Array | int = 0,
    *,
    payload=None,
    outputs=None,
):
    """DEPRECATED shim: vmap over seeds.

    Use ``repro.api.Experiment(graph=..., protocol=pcfg, failures=fcfg,
    steps=steps, ...).ensemble(seeds, base_key)``.
    """
    from repro.api import Experiment
    from repro.utils.deprecation import warn_legacy_runner

    warn_legacy_runner(
        "repro.core.run_ensemble", "Experiment(...).ensemble(seeds)"
    )
    return Experiment(
        graph=graph, protocol=pcfg, failures=fcfg, steps=steps,
        payload=payload, outputs=outputs,
    ).ensemble(seeds, base_key)


def run_sweep(
    graph: Graph,
    scenarios: Sequence[Tuple[prt.ProtocolConfig, flr.FailureConfig]],
    steps: int,
    seeds: int,
    base_key: jax.Array | int = 0,
    *,
    sharded: bool | None = None,
    payload=None,
    outputs=None,
):
    """DEPRECATED shim: one static-structure scenario stack x seeds,
    stacked outputs with leading (S, seeds) axes.

    Use ``repro.api.Experiment(graph=..., scenarios=..., steps=...,
    placement=...).plan().sweep_stacked(seeds=seeds, base_key=...)``
    (the ``sharded`` tri-state maps to ``Placement.from_sharded``).
    """
    from repro.api import Experiment, Placement
    from repro.utils.deprecation import warn_legacy_runner

    warn_legacy_runner(
        "repro.core.simulator.run_sweep",
        "Experiment(...).plan().sweep_stacked(seeds=...)",
    )
    return Experiment(
        graph=graph, scenarios=scenarios, steps=steps, payload=payload,
        outputs=outputs, placement=Placement.from_sharded(sharded),
    ).plan().sweep_stacked(seeds=seeds, base_key=base_key)


# ---------------------------------------------------------------------------
# Trajectory metrics (used by benchmarks and integration tests)
# ---------------------------------------------------------------------------


def reaction_time(z, z0: int, failure_time: int) -> int:
    """Steps from `failure_time` until Z_t first returns to >= z0 (-1: never)."""
    import numpy as np

    z = np.asarray(z)
    post = z[failure_time:]
    hits = np.nonzero(post >= z0)[0]
    return int(hits[0]) if hits.size else -1


def max_overshoot(z, z0: int) -> int:
    import numpy as np

    return int(np.max(np.asarray(z)) - z0)


def survived(z) -> bool:
    """Resilience objective: at least one walk alive at all times."""
    import numpy as np

    return bool((np.asarray(z) > 0).all())
