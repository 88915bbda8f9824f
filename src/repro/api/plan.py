"""Compiled execution plans: the layer between an Experiment and XLA.

An :class:`~repro.api.Experiment` *describes* a study; ``plan()`` lowers
it into a :class:`Plan` that owns the three things the four legacy
runners used to split between themselves and their callers:

  1. **static-signature grouping** — which scenario rows can share one
     compiled program (``Plan.groups``; the orchestration that lived in
     ``sweep/engine.run_scenarios``);
  2. **the compile cache** — a process-wide table of jitted executables
     keyed on :func:`plan_signature`, so the same static structure never
     re-lowers across ``.run`` / ``.ensemble`` / ``.sweep`` calls, across
     re-planned Experiments, across figures (``cache_stats`` exposes the
     entry and XLA-compile counts the tests assert on);
  3. **the placement decision** — ``Placement`` applied to the stacked
     scenario leaves at exactly one point.

The executables are jitted wrappers over the three un-jitted cores in
``core/simulator.py`` (one trajectory / vmap over seeds / vmap over
(scenario, seed)); everything traces through the same ``_run_core``, so
``sweep(...)[i]`` == ``ensemble`` on scenario ``i`` == the single
``run``, bitwise, under the same base key.
"""
from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.api.placement import Placement
from repro.api.results import SweepResult
from repro.core import simulator as sim
from repro.graphs.spectral import stationary_distribution
from repro.graphs.state import mirror_indices
from repro.utils.faults import fault_point

__all__ = [
    "Plan",
    "plan_signature",
    "cache_stats",
    "clear_cache",
]

_STATIC_ARGNAMES = ("steps", "n", "payload", "spec", "pspec")
_SEG_STATIC_ARGNAMES = ("seg_len",) + _STATIC_ARGNAMES
_CORES = {
    "run": sim._run_core,
    "ensemble": sim._run_ensemble_core,
    "sweep": sim._sweep_core,
    # durable-execution segment cores: carry -> (carry', recorded chunk)
    "seg_run": sim._seg_run_core,
    "seg_ensemble": sim._seg_ensemble_core,
    "seg_sweep": sim._seg_sweep_core,
}
_MODE_STATICS = {
    mode: (_SEG_STATIC_ARGNAMES if mode.startswith("seg_") else _STATIC_ARGNAMES)
    + (("mesh",) if mode.endswith("sweep") else ())
    for mode in _CORES
}

# the process-wide compile cache: (mode, signature) -> jitted executable.
# One slot per static program structure; the executables themselves are
# shared per mode (_JITTED) — jax keys the underlying compilation cache
# on (static kwargs, avals), so distinct signatures compile distinct XLA
# programs through one wrapper, and re-running the same structure never
# re-lowers or recompiles.
_EXECUTABLES: dict = {}
_JITTED: dict = {}


def payload_key(payload):
    """The signature component identifying a payload's static program.

    A payload declaring a stable :meth:`~repro.core.payload.Payload.signature`
    contributes a value tuple — two structurally identical payload
    instances then share one cache slot (and, since ``Payload.__eq__``
    follows the same key, one compiled XLA program), and the tuple is
    serializable for cross-process result-store keys. A signature-less
    payload contributes the object itself (identity hashing, the
    pre-signature behavior).
    """
    if payload is None:
        return None
    key = getattr(payload, "_signature_key", lambda: None)()
    return payload if key is None else ("payload",) + key


def plan_signature(
    mode: str,
    n: int,
    max_deg: int,
    steps: int,
    pcfg,
    schedule_lens: Tuple[int, ...],
    payload,
    spec,
    pspec,
    fcfg_static: tuple = (),
) -> tuple:
    """Hashable static signature of one compiled program.

    Two runs share an executable iff their signatures match: program
    shape comes from the protocol's static fields (algorithm /
    estimator_impl / max_walks / rt_bins / walk_variant / ...), the
    pytree structure of ``fork_prob`` (None vs value), the padded
    failure-schedule lengths (bursts, node crashes, extra Pac-Man ids,
    edge cuts), the failure config's static aux fields
    (``pacman_mobile`` — it changes the scan carry), the payload's
    :func:`payload_key` (a stable config tuple when the payload declares
    ``signature()``, the identity-hashed object otherwise), the output
    specs and the graph/trajectory dimensions. Traced numeric leaves
    (eps grids, rates, schedules, topology knobs) deliberately do NOT
    appear — they batch and re-run without recompiling.
    """
    return (
        mode,
        n,
        max_deg,
        steps,
        pcfg.static_fields,
        pcfg.fork_prob is None,
        tuple(schedule_lens),
        payload_key(payload),
        spec,
        pspec,
        tuple(fcfg_static),
    )


def _lower(mode: str, signature: tuple):
    """Resolve the executable for one NEW (mode, signature) cache slot.

    Called exactly once per fresh signature — the module-level seam the
    compile-count tests monkeypatch. The returned wrapper is shared per
    mode: jax's own cache keys compiled programs on (static kwargs,
    avals), which the signature mirrors, so slot bookkeeping and program
    caching agree.
    """
    fn = _JITTED.get(mode)
    if fn is None:
        fn = _JITTED[mode] = jax.jit(
            _CORES[mode], static_argnames=_MODE_STATICS[mode]
        )
    return fn


def executable(mode: str, signature: tuple):
    """The process-wide cache lookup: one jitted executable per
    (mode, static-signature), built on first use."""
    key = (mode, signature)
    fn = _EXECUTABLES.get(key)
    if fn is None:
        fn = _EXECUTABLES[key] = _lower(mode, signature)
    return fn


def cache_stats() -> dict:
    """Observability for the compile cache: ``entries`` is the number of
    distinct (mode, signature) slots ever lowered; ``xla_compiles`` the
    total number of XLA programs actually compiled (one per distinct
    (signature, batch shape) — a structure recompiles only for a new
    aval shape, e.g. a different seed count); ``by_mode`` splits the
    compile count per execution mode (run / ensemble / sweep).
    """
    by_mode = {m: f._cache_size() for m, f in _JITTED.items()}
    return {
        "entries": len(_EXECUTABLES),
        "xla_compiles": sum(by_mode.values()),
        "by_mode": by_mode,
    }


def clear_cache() -> None:
    """Drop every cached executable (tests only — a cleared cache means
    every structure re-lowers and recompiles on next use)."""
    _EXECUTABLES.clear()
    _JITTED.clear()


def _as_key(key) -> jax.Array:
    return jax.random.key(key) if isinstance(key, int) else key


def _schedule_lens(fcfg) -> tuple:
    """The shape-bearing failure-schedule lengths, in signature order."""
    return (
        fcfg.n_bursts, fcfg.n_node_crashes, fcfg.n_pacman, fcfg.n_edge_cuts
    )


class Plan:
    """A compiled execution plan for one Experiment (see module docstring).

    Construct via ``Experiment.plan()``. Methods:

      ``run(key=0)``                     one trajectory of the base
                                         (protocol, failures) scenario;
      ``ensemble(seeds, base_key=0)``    vmap over seeds;
      ``sweep_stacked(scenarios=None, *, seeds, base_key=0)``
                                         ONE static-structure stack ->
                                         outputs with leading (S, seeds)
                                         axes in one compiled call;
      ``sweep(scenarios=None, *, seeds, base_key=0)``
                                         arbitrary mixed lists: grouped by
                                         static signature, one compiled
                                         call per group, per-scenario
                                         results in input order
                                         (:class:`SweepResult`).

    All four share the process-wide executable cache, so re-running any
    of them with the same static structure — new keys, new eps grids, new
    failure rates, a re-planned Experiment — never recompiles.
    """

    def __init__(self, experiment):
        from repro.sweep.scenario import as_pair

        self.experiment = experiment
        self.graph = experiment.graph
        self.steps = experiment.steps
        self.payload = experiment.payload
        self.placement = experiment.placement
        self.spec = experiment._spec
        self.pspec = experiment._pspec
        self.n = self.graph.n
        self.neighbors = jnp.asarray(self.graph.neighbors)
        self.degrees = jnp.asarray(self.graph.degrees)
        self.mirror = jnp.asarray(mirror_indices(self.graph))
        self.max_deg = int(self.neighbors.shape[1])
        self._pi_cache = None
        if experiment.protocol is not None:
            self._base = (experiment.protocol, experiment.failures)
            if self.payload is not None:
                self.payload.validate(experiment.protocol)
        else:
            self._base = None
        # eager static validation of declared scenario rows
        for s in experiment.scenarios or ():
            pcfg, _ = as_pair(s)
            if self.payload is not None:
                self.payload.validate(pcfg)

    # -- shared preparation ------------------------------------------------

    def _pi(self, pcfg):
        if not pcfg.analytic_survival:
            return None
        if self._pi_cache is None:
            self._pi_cache = jnp.asarray(
                stationary_distribution(self.graph), jnp.float32
            )
        return self._pi_cache

    def _signature(self, mode, pcfg, schedule_lens, fcfg=None):
        return plan_signature(
            mode, self.n, self.max_deg, self.steps, pcfg,
            schedule_lens, self.payload, self.spec, self.pspec,
            fcfg_static=() if fcfg is None else fcfg.static_fields,
        )

    def _require_base(self, what: str):
        if self._base is None:
            raise ValueError(
                f"Plan.{what} needs a base scenario: construct the "
                "Experiment with protocol=/failures= (or use .sweep on its "
                "scenarios)"
            )
        return self._base

    # -- execution ---------------------------------------------------------

    def run(self, key: jax.Array | int = 0):
        """One trajectory; returns ``(final SimState, RecordedOutputs)``
        (with a payload: ``((state, payload carry), (RecordedOutputs,
        payload outputs))``)."""
        pcfg, fcfg = self._require_base("run")
        sig = self._signature("run", pcfg, _schedule_lens(fcfg), fcfg)
        return executable("run", sig)(
            _as_key(key), self.neighbors, self.degrees, self.mirror,
            self._pi(pcfg), pcfg, fcfg,
            steps=self.steps, n=self.n, payload=self.payload,
            spec=self.spec, pspec=self.pspec,
        )

    def ensemble(self, seeds: int, base_key: jax.Array | int = 0):
        """vmap over seeds: outputs with a leading ``(seeds,)`` axis."""
        pcfg, fcfg = self._require_base("ensemble")
        keys = jax.random.split(_as_key(base_key), seeds)
        sig = self._signature("ensemble", pcfg, _schedule_lens(fcfg), fcfg)
        return executable("ensemble", sig)(
            keys, self.neighbors, self.degrees, self.mirror,
            self._pi(pcfg), pcfg, fcfg,
            steps=self.steps, n=self.n, payload=self.payload,
            spec=self.spec, pspec=self.pspec,
        )

    # -- durable segmented execution ---------------------------------------
    #
    # The segmented path splits one scan into ``ceil(steps/segment_steps)``
    # compiled chunks through the ``seg_*`` cores. Because every PRNG
    # stream folds the CARRIED step counter (never a scan index), the
    # chunked trajectory is bitwise the monolithic one — the golden
    # resume tests hold this invariant. With a store, each boundary
    # write-behinds a self-contained snapshot (carry + recorded-so-far)
    # under the run's content key, so a killed process resumes from the
    # deepest loadable snapshot regardless of the chunking it now uses.

    def _segment_store(self, store, sig, stacked_configs, seeds, base):
        from repro.api.store import ResultStore

        store = ResultStore.resolve(store)
        if store is None:
            return None, None
        skey = store.sweep_key(sig, self.graph, stacked_configs, seeds, base)
        return store, skey

    def _drive_segments(
        self, mode, sig, init_carry, cfg_args, segment_steps, time_axis,
        store, skey, **statics,
    ):
        """Run one segmented trajectory/ensemble/sweep to completion.

        ``init_carry`` is a thunk (only called when no resumable snapshot
        exists); ``cfg_args`` is ``(pi, pcfg(s), fcfg(s))``;
        ``time_axis`` is where recorded chunks concatenate (run: 0,
        ensemble: 1, sweep: 2); ``statics`` pass through to every chunk
        (the sweep's scenario ``mesh``). Snapshot writes are best-effort — a
        failing store degrades to lost progress, never a failed run —
        and fault site ``segment.boundary`` fires after every boundary.
        """
        segment_steps = int(segment_steps)
        if segment_steps < 1:
            raise ValueError(f"segment_steps must be >= 1, got {segment_steps}")
        steps = self.steps
        done, carry, recorded = 0, None, None
        if store is not None:
            found = store.latest_segment(skey, max_steps=steps)
            if found is not None:
                done, snap = found
                carry, recorded = snap["carry"], snap["recorded"]
        if carry is None:
            carry = init_carry()
        while done < steps:
            seg = min(segment_steps, steps - done)
            seg_sig = sig + (("seg_len", seg),)
            carry, chunk = executable(mode, seg_sig)(
                carry, self.neighbors, self.degrees, self.mirror, *cfg_args,
                seg_len=seg, steps=steps, n=self.n, payload=self.payload,
                spec=self.spec, pspec=self.pspec, **statics,
            )
            recorded = chunk if recorded is None else jax.tree_util.tree_map(
                lambda a, b: jnp.concatenate((a, b), axis=time_axis),
                recorded, chunk,
            )
            done += seg
            if store is not None and done < steps:
                try:
                    store.put_segment(
                        skey, done,
                        jax.block_until_ready(
                            {"carry": carry, "recorded": recorded}
                        ),
                        extra_meta={"mode": mode, "total_steps": steps},
                    )
                except Exception as exc:  # write-behind is best-effort
                    import warnings

                    warnings.warn(
                        f"segment write-behind failed at {done}/{steps} "
                        f"steps: {exc!r}"
                    )
            fault_point("segment.boundary")
        return carry, recorded

    def run_segmented(
        self, key: jax.Array | int = 0, *, segment_steps: int, store=None
    ):
        """:meth:`run`, executed in resumable segments — same return
        value, bitwise. ``store=`` enables boundary snapshots (and
        resume from them); on completion the snapshots are cleared."""
        pcfg, fcfg = self._require_base("run_segmented")
        base = _as_key(key)
        sig = self._signature("seg_run", pcfg, _schedule_lens(fcfg), fcfg)
        store, skey = self._segment_store(store, sig, (pcfg, fcfg), 1, base)
        carry, recorded = self._drive_segments(
            "seg_run", sig,
            lambda: sim._init_carry(
                base, self.neighbors, pcfg, fcfg, self.steps, self.n,
                self.payload,
            ),
            (self._pi(pcfg), pcfg, fcfg), segment_steps, 0, store, skey,
        )
        if store is not None:
            store.clear_segments(skey)
        final = sim._finalize_segmented(carry, self.n, pcfg, self.payload)
        return final, recorded

    def ensemble_segmented(
        self,
        seeds: int,
        base_key: jax.Array | int = 0,
        *,
        segment_steps: int,
        store=None,
    ):
        """:meth:`ensemble` in resumable segments — same outputs,
        bitwise (leading ``(seeds,)`` axis, time on axis 1)."""
        pcfg, fcfg = self._require_base("ensemble_segmented")
        base = _as_key(base_key)
        keys = jax.random.split(base, seeds)
        sig = self._signature("seg_ensemble", pcfg, _schedule_lens(fcfg), fcfg)
        store, skey = self._segment_store(
            store, sig, (pcfg, fcfg), seeds, base
        )
        _carry, recorded = self._drive_segments(
            "seg_ensemble", sig,
            lambda: sim._init_ensemble_carry(
                keys, self.neighbors, pcfg, fcfg, self.steps, self.n,
                self.payload,
            ),
            (self._pi(pcfg), pcfg, fcfg), segment_steps, 1, store, skey,
        )
        if store is not None:
            store.clear_segments(skey)
        return recorded

    def sweep_stacked(
        self,
        scenarios: Sequence | None = None,
        *,
        seeds: int,
        base_key: jax.Array | int = 0,
        store=None,
        segment_steps: int | None = None,
    ):
        """One static-structure scenario stack x seeds in ONE compiled
        call; outputs carry leading ``(S, seeds)`` axes.

        Every scenario uses the same per-seed keys ``ensemble`` derives
        from ``base_key``, so ``sweep_stacked(...)[i]`` is bitwise equal
        to ``ensemble`` on scenario ``i``. Scenarios must share one
        static signature (mixed lists: use :meth:`sweep`); the Plan's
        ``Placement`` decides scenario-axis device placement here.

        ``store=`` (None | ``'env'`` | path | ``ResultStore``) enables
        disk-backed result persistence: a store-warm call returns the
        cached pytree without tracing, compiling or executing anything —
        the content key covers the plan signature, the graph, every
        stacked scenario leaf, ``seeds`` and the base key material.

        ``segment_steps=`` switches to the durable segmented executor:
        the scan runs in resumable chunks (bitwise identical to the
        monolithic call), and with a store each boundary write-behinds a
        snapshot so a killed process resumes a half-finished sweep from
        disk. The final result lands under the SAME content key as the
        monolithic path — segmented and monolithic warm hits are
        interchangeable — and ``segment_steps`` itself never enters the
        store key (only the per-chunk compile signatures).
        """
        from repro.sweep.scenario import as_pair, stack_configs

        scenarios = self._scenarios(scenarios, "sweep_stacked")
        base = _as_key(base_key)
        pcfgs, fcfgs = stack_configs(scenarios)
        pcfg0 = as_pair(scenarios[0])[0]
        if self.payload is not None:
            self.payload.validate(pcfg0)
        # schedule lengths AFTER stacking: pad_bursts reconciled them
        lens = (
            int(jnp.shape(fcfgs.burst_times)[-1]),
            int(jnp.shape(fcfgs.node_crash_times)[-1]),
            int(jnp.shape(fcfgs.pacman_nodes)[-1]),
            int(jnp.shape(fcfgs.edge_cut_times)[-1]),
        )
        sig = self._signature("sweep", pcfg0, lens, fcfgs)

        from repro.api.store import ResultStore

        store = ResultStore.resolve(store)
        skey = None
        if store is not None:
            # key on the pre-placement stacked leaves: device placement
            # never changes the answer, so it must not change the key
            skey = store.sweep_key(sig, self.graph, (pcfgs, fcfgs), seeds, base)
            cached = store.get(skey)
            if cached is not None:
                return cached

        keys = jax.random.split(base, seeds)
        pcfgs, fcfgs, mesh = self.placement.place(
            pcfgs, fcfgs, len(scenarios)
        )
        if segment_steps is None:
            result = executable("sweep", sig)(
                keys, self.neighbors, self.degrees, self.mirror,
                self._pi(pcfg0), pcfgs, fcfgs,
                steps=self.steps, n=self.n, payload=self.payload,
                spec=self.spec, pspec=self.pspec, mesh=mesh,
            )
        else:
            seg_sig = self._signature("seg_sweep", pcfg0, lens, fcfgs)
            _carry, result = self._drive_segments(
                "seg_sweep", seg_sig,
                lambda: sim._init_sweep_carry(
                    keys, self.neighbors, pcfgs, fcfgs, self.steps, self.n,
                    self.payload,
                ),
                (self._pi(pcfg0), pcfgs, fcfgs), segment_steps, 2,
                store, skey, mesh=mesh,
            )
        if store is not None:
            store.put(
                skey,
                jax.block_until_ready(result),
                extra_meta={"scenarios": len(scenarios), "seeds": int(seeds)},
            )
            if segment_steps is not None:
                store.clear_segments(skey)
        return result

    def sweep(
        self,
        scenarios: Sequence | None = None,
        *,
        seeds: int,
        base_key: jax.Array | int = 0,
        store=None,
        segment_steps: int | None = None,
    ) -> SweepResult:
        """Run a mixed scenario list: grouped by static signature, ONE
        compiled call per group, per-scenario results in input order.

        Each scenario's ``(seeds,)``-leading outputs are bitwise what
        ``ensemble`` would produce for it under the same ``base_key``;
        adding a new regime (failure schedule, topology churn, Pac-Man
        node, eps grid row) is appending a scenario row, not a new
        compilation unit. ``store=`` persists each group's stacked call
        (see :meth:`sweep_stacked`).
        """
        scenarios = self._scenarios(scenarios, "sweep")
        names = tuple(
            getattr(s, "name", f"scenario{i}") for i, s in enumerate(scenarios)
        )
        results = [None] * len(scenarios)
        payloads = [None] * len(scenarios) if self.payload is not None else None
        for _sig, idxs in self.groups(scenarios):
            stacked = self.sweep_stacked(
                [scenarios[i] for i in idxs], seeds=seeds, base_key=base_key,
                store=store, segment_steps=segment_steps,
            )
            if self.payload is not None:
                stacked, stacked_payload = stacked
            for j, i in enumerate(idxs):
                results[i] = jax.tree_util.tree_map(lambda x: x[j], stacked)
                if self.payload is not None:
                    payloads[i] = jax.tree_util.tree_map(
                        lambda x: x[j], stacked_payload
                    )
        return SweepResult(names=names, outputs=results, payloads=payloads)

    # -- introspection -----------------------------------------------------

    def round_decisions(self, scenarios: Sequence | None = None) -> list:
        """How each compile group executes its rounds — with the reason.

        Returns ``[(signature, indices, RoundDecision)]`` over the given
        (or the Experiment's) scenario list; for a base-only plan (no
        scenario rows) a single entry with ``signature=None`` and
        ``indices=[0]``. The :class:`~repro.core.simulator.RoundDecision`
        carries ``impl`` (``'fused'``/``'unfused'``), the fused backend,
        and the ``reason`` string — the observability hook for configs
        that silently fall back to the stage sequence (zoo walk variants,
        attack statics outside a kernel's support). The decision is made
        on the group's PADDED schedule widths, exactly as the compiled
        program sees them: a cut-free scenario co-batched with an
        edge-cut scenario shares its group's fallback.
        """
        from repro.core.failures import pad_bursts
        from repro.core.simulator import round_impl_decision
        from repro.sweep.scenario import as_pair

        if scenarios is None and not self.experiment.scenarios:
            pcfg, fcfg = self._require_base("round_decisions")
            return [(None, [0], round_impl_decision(pcfg, fcfg))]
        scenarios = self._scenarios(scenarios, "round_decisions")
        out = []
        for sig, idxs in self.groups(scenarios):
            pairs = [as_pair(scenarios[i]) for i in idxs]
            fcfgs = pad_bursts([f for _, f in pairs])
            out.append((sig, idxs, round_impl_decision(pairs[0][0], fcfgs[0])))
        return out

    def groups(self, scenarios: Sequence | None = None) -> list:
        """The static-signature grouping: ``[(signature, [indices])]``
        over the given (or the Experiment's) scenario list — which rows
        share one compiled program."""
        from repro.sweep.scenario import group_scenarios

        return group_scenarios(self._scenarios(scenarios, "groups"))

    def _scenarios(self, scenarios, what: str) -> list:
        scenarios = (
            self.experiment.scenarios if scenarios is None else scenarios
        )
        if not scenarios:
            raise ValueError(
                f"Plan.{what} needs scenarios: pass them to the call or "
                "construct the Experiment with scenarios=[...]"
            )
        return list(scenarios)

    def __repr__(self):
        base = "1 base scenario" if self._base else "no base scenario"
        ns = len(self.experiment.scenarios or ())
        return (
            f"Plan(n={self.n}, steps={self.steps}, {base}, "
            f"{ns} declared scenario(s), placement={self.placement.policy!r})"
        )
