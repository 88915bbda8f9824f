"""The one traffic generator: a traffic file's parameters -> studies.

A study is what a researcher submits and waits for: one protocol of the
configuration, run as an ensemble of ``seeds`` trajectories from one base
key. A traffic file (``traffic/<mix>.json``) names the protocols in the
order they are submitted, the seeds per study and how many trajectories
of each finished study the reference checks. Base keys come from
``--seed``: the same seed gives the same studies, and every seed gives the
same sizes in the same order.
"""
from __future__ import annotations

import dataclasses
import itertools

import numpy as np

KEY_STREAM, WARM_STREAM, CHECK_STREAM = 0, 1, 2


@dataclasses.dataclass(frozen=True)
class Study:
    index: int
    protocol: str
    seeds: int
    base_key: int


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([stream, seed % 2**64]))


def _base_keys(seed: int, stream: int):
    r = rng(seed, stream)
    while True:
        yield int(r.integers(0, 2**31))


def studies(traffic: dict, seed: int):
    """The endless sequence of studies this mix submits, back to back."""
    keys = _base_keys(seed, KEY_STREAM)
    cycle = itertools.cycle(s["protocol"] for s in traffic["studies"])
    for i, proto in enumerate(cycle):
        yield Study(i, proto, traffic["seeds"], next(keys))


def warmups(traffic: dict, seed: int) -> list:
    """One study per distinct protocol, on keys the window never uses."""
    keys = _base_keys(seed, WARM_STREAM)
    protos = dict.fromkeys(s["protocol"] for s in traffic["studies"])
    return [Study(-1 - i, p, traffic["seeds"], next(keys)) for i, p in enumerate(protos)]


def sample(traffic: dict, seed: int, studies_done: list) -> list:
    """``(study, trajectory)`` pairs the reference checks: per finished
    study, one trajectory drawn from each of ``check.per_study`` equal
    strata of its seeds, so every part of the batch is looked at."""
    r = rng(seed, CHECK_STREAM)
    k = traffic["check"]["per_study"]
    picks = []
    for st in studies_done:
        edges = np.linspace(0, st.seeds, k + 1).astype(int)
        picks += [(st, int(r.integers(lo, hi))) for lo, hi in zip(edges, edges[1:])]
    return picks
