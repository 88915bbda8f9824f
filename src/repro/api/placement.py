"""Device-placement policy for the scenario axis.

:class:`Placement` replaces the legacy ``sharded=`` tri-state (and the
ad-hoc mesh probing that lived in ``sweep/engine.py``) with an explicit,
named policy object the :class:`~repro.api.Plan` owns:

  ``Placement.AUTO``     place the stacked scenario leaves across the
                         'data' axis of the local mesh when more than one
                         device is visible and the scenario count
                         divides; silently stay local otherwise —
                         correctness never depends on placement.
  ``Placement.SHARDED``  demand placement; raise when it cannot be
                         honored instead of silently running replicated.
  ``Placement.LOCAL``    never touch device placement.

A placed sweep runs each device's block of scenarios under ``shard_map``
(``core.simulator._split_scenarios``): the round's Mosaic kernel cannot
be partitioned automatically, and scenarios never exchange data.

Policies are tiny frozen values: pass one to ``Experiment(placement=...)``
(strings ``"auto"`` / ``"sharded"`` / ``"local"`` also accepted).
``Placement.from_sharded`` maps the legacy tri-state — ``None`` -> AUTO,
``True`` -> SHARDED, ``False`` -> LOCAL — with the same identity-based
validation (``0``/``1`` must not alias into the wrong policy).
"""
from __future__ import annotations

import dataclasses

import jax

__all__ = ["Placement"]

_POLICIES = ("auto", "sharded", "local")


@dataclasses.dataclass(frozen=True)
class Placement:
    """Scenario-axis device-placement policy (see module docstring)."""

    policy: str = "auto"

    def __post_init__(self):
        if self.policy not in _POLICIES:
            raise ValueError(
                f"unknown placement policy {self.policy!r}; use one of "
                f"{list(_POLICIES)}"
            )

    # -- construction ------------------------------------------------------

    @classmethod
    def resolve(cls, value) -> "Placement":
        """Normalize an ``Experiment(placement=...)`` argument."""
        if value is None:
            return cls.AUTO
        if isinstance(value, cls):
            return value
        if isinstance(value, str):
            cls(value)  # validate the name
            return {p.policy: p for p in (cls.AUTO, cls.SHARDED, cls.LOCAL)}[
                value
            ]
        raise TypeError(
            f"placement must be a Placement or one of {list(_POLICIES)}; "
            f"got {value!r}"
        )

    @classmethod
    def from_sharded(cls, sharded) -> "Placement":
        """Map the legacy ``sharded=`` tri-state to a policy.

        Identity, not equality: 0/1 must not alias False/True into the
        wrong placement path (0 == False but ``0 is not False`` would
        have fallen through to auto).
        """
        if sharded is None:
            return cls.AUTO
        if sharded is True:
            return cls.SHARDED
        if sharded is False:
            return cls.LOCAL
        raise TypeError(
            f"sharded must be True, False or None (auto); got {sharded!r}"
        )

    # -- the decision ------------------------------------------------------

    def place(self, pcfgs, fcfgs, n_scenarios: int):
        """Place stacked config leaves across the 'data' mesh axis per
        this policy. Returns ``(pcfgs, fcfgs, mesh)``: the (possibly
        device_put) config pytrees, and the mesh whose 'data' axis splits
        the scenario axis — None when the scenarios stay where they are
        (local policy, one device, or an auto policy whose scenario count
        does not divide the devices). The sweep cores run each device's
        scenario block under ``shard_map`` on that mesh.
        """
        if self.policy == "local":
            return pcfgs, fcfgs, None
        explicit = self.policy == "sharded"
        if jax.device_count() == 1:
            return pcfgs, fcfgs, None
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.launch.mesh import data_axis_size, make_local_mesh

        mesh = make_local_mesh()
        if n_scenarios % data_axis_size(mesh) != 0:
            if explicit:
                raise ValueError(
                    f"placement='sharded' but {n_scenarios} scenarios do not "
                    f"divide the data axis ({data_axis_size(mesh)} devices); "
                    "pad the scenario list or use Placement.AUTO"
                )
            return pcfgs, fcfgs, None
        sharding = NamedSharding(mesh, P("data"))

        def put(x):
            return jax.device_put(x, sharding)

        return (
            jax.tree_util.tree_map(put, pcfgs),
            jax.tree_util.tree_map(put, fcfgs),
            mesh,
        )


Placement.AUTO = Placement("auto")
Placement.SHARDED = Placement("sharded")
Placement.LOCAL = Placement("local")
