"""Legacy sweep-engine surface (deprecated shims over ``repro.api``).

The batched scenario engine this module used to implement — grouping by
static signature, one compiled call per group, scenario-axis placement —
now lives in :class:`repro.api.Plan` (grouping + compile cache) and
:class:`repro.api.Placement` (the placement decision). What remains here:

  * :func:`run_scenarios` — a deprecation shim building the equivalent
    ``Experiment(...).sweep(...)`` (bitwise-equal by construction);
  * :func:`maybe_shard_scenarios` — a thin delegate to ``Placement``,
    kept for callers of the old helper;
  * ``SweepResult`` — re-exported from ``repro.api.results`` (its new
    home) so existing imports keep resolving.
"""
from __future__ import annotations

from typing import Sequence

import jax

from repro.api.results import SweepResult

__all__ = ["SweepResult", "run_scenarios", "maybe_shard_scenarios"]


def maybe_shard_scenarios(pcfgs, fcfgs, n_scenarios: int, *, explicit: bool = False):
    """Place stacked config leaves across the 'data' mesh axis.

    Thin delegate to :meth:`repro.api.Placement.place` (where the logic
    moved): ``explicit=False`` is ``Placement.AUTO``, ``explicit=True``
    is ``Placement.SHARDED``.
    """
    from repro.api import Placement

    policy = Placement.SHARDED if explicit else Placement.AUTO
    return policy.place(pcfgs, fcfgs, n_scenarios)[:2]


def run_scenarios(
    graph,
    scenarios: Sequence,
    steps: int,
    seeds: int,
    base_key: jax.Array | int = 0,
    *,
    sharded: bool | None = None,
    payload=None,
    outputs=None,
) -> SweepResult:
    """DEPRECATED shim: run a mixed scenario list, one compiled call per
    static group, per-scenario results in input order.

    Use ``repro.api.Experiment(graph=..., scenarios=..., steps=...,
    placement=...).sweep(seeds=...)`` — same grouping, same compile
    caching, same bits.
    """
    from repro.api import Experiment, Placement
    from repro.utils.deprecation import warn_legacy_runner

    warn_legacy_runner(
        "repro.sweep.run_scenarios", "Experiment(...).sweep(seeds=...)"
    )
    return Experiment(
        graph=graph, scenarios=scenarios, steps=steps, payload=payload,
        outputs=outputs, placement=Placement.from_sharded(sharded),
    ).sweep(seeds=seeds, base_key=base_key)
