"""Faults planted in the timed path, to show that ``correct`` catches them.

Each fault breaks the program underneath a whole run, the way a wrong
optimisation would: a round or a local step that returns its state
unchanged, half of the batch left out with the mean taken over the rest,
an answer altered where it is produced. A cell with a learning payload
takes the faults its kind plants in the payload (``payloads/<kind>.py``,
``faults()``; for ``rwsgd`` these three in the replicas' step, a fork
that leaves the child's replica uncopied, and a step count that counts
dead slots as trained). (Every cell runs on one chip, so no exchange
between chips can be left out.) Used by the tests on the CPU and by
``control.py`` on the chip.
"""
from __future__ import annotations

import contextlib


def _sweep_faults():
    import jax
    import jax.numpy as jnp

    from repro.api import plan
    from repro.core import simulator as sim

    step = sim.protocol_step
    core = plan._CORES["ensemble"]

    def unchanged_state(state, *a, **k):
        new, out = step(state, *a, **k)
        return state._replace(t=new.t), out

    def altered_answer(state, *a, **k):
        new, out = step(state, *a, **k)
        return new, out._replace(forks=out.forks + 1)

    def half_batch(keys, neighbors, degrees, mirror, pi, pcfg, fcfg, steps, n,
                   payload=None, spec=sim.SCALARS, pspec=None):
        h = keys.shape[0] // 2
        out = core(keys[:h], neighbors, degrees, mirror, pi, pcfg, fcfg, steps, n,
                   payload, spec, pspec)
        return jax.tree.map(lambda x: jnp.concatenate([x, x])[: keys.shape[0]], out)

    return {
        "unchanged_state": [(sim, "protocol_step", unchanged_state)],
        "half_batch": [(plan._CORES, "ensemble", half_batch)],
        "altered_answer": [(sim, "protocol_step", altered_answer)],
    }


NAMES = ("unchanged_state", "half_batch", "altered_answer")


def names(kind) -> tuple:
    """The faults a cell can have: its payload kind's (``payloads.Kind``),
    or the sweep's where it has none."""
    return NAMES if kind is None else tuple(kind.faults())


def _set(obj, name, value):
    if isinstance(obj, dict):
        obj[name] = value
    else:
        setattr(obj, name, value)


@contextlib.contextmanager
def planted(kind, fault: str | None):
    """Run the body with ``fault`` planted in the program (None: none),
    tracing and compiling anew on the way in and out; ``kind`` as for
    ``names``."""
    import jax

    from repro.api import plan

    patches = []
    if fault is not None:
        table = _sweep_faults() if kind is None else kind.faults()
        patches = table[fault]
    saved = [(o, n, o[n] if isinstance(o, dict) else getattr(o, n)) for o, n, _ in patches]
    plan.clear_cache()
    jax.clear_caches()
    try:
        for o, n, v in patches:
            _set(o, n, v)
        yield
    finally:
        for o, n, v in saved:
            _set(o, n, v)
        plan.clear_cache()
        jax.clear_caches()
