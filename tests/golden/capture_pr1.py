"""Capture golden PR-1 trajectories for the topology no-op equivalence tests.

Writes ``tests/golden/pr1_trajectories.json``; ``tests/test_topology.py``
then asserts that the simulator with every topology-failure knob disabled
reproduces these outputs bitwise (as do the payload and zoo neutrality
tests).

    PYTHONPATH=src python tests/golden/capture_pr1.py

The trajectories depend on the toolchain as well as on the code: the
PRNG bits follow ``jax_threefry_partitionable`` (its default flipped to
True in jax 0.5), and float reductions follow XLA's CPU codegen (a
theta_mean can move by an ulp when the order of a sum changes). The JSON
records the jax/jaxlib versions and PRNG settings it was captured under
in its ``toolchain`` entry; re-capture only when a toolchain change, not
a code change, moved the trajectories.
"""
from __future__ import annotations

import json
import os

import numpy as np

import jax
import jaxlib

from repro.api import Experiment
from repro.core import FailureConfig, ProtocolConfig
from repro.graphs import random_regular_graph

OUT = os.path.join(os.path.dirname(__file__), "pr1_trajectories.json")

# mirror tests/test_topology.py: keep these literals in sync
N, DEG, GRAPH_SEED = 24, 4, 3
W, Z0, STEPS, SEEDS, BASE_KEY = 10, 5, 60, 2, 7


def _pcfg(alg, **kw):
    base = dict(algorithm=alg, z0=Z0, max_walks=W, rt_bins=32, protocol_start=10)
    base.update(kw)
    return ProtocolConfig(**base)


def cases():
    burst = FailureConfig(burst_times=(20,), burst_sizes=(2,))
    byz = FailureConfig(
        burst_times=(25,), burst_sizes=(1,), p_fail=0.002,
        byzantine_node=1, p_byz=0.01, byz_start_time=15,
    )
    return [
        ("decafork/burst", _pcfg("decafork", eps=1.8), burst),
        ("decafork+/byz", _pcfg("decafork+", eps=1.6, eps2=6.0), byz),
        ("missingperson/burst", _pcfg("missingperson", eps_mp=20.0), burst),
        ("none/pfail", _pcfg("none"), FailureConfig(p_fail=0.004)),
    ]


def _outputs_to_dict(outs) -> dict:
    # float32 -> python float is exact (float64 widening), so the JSON
    # round-trip preserves bitwise equality for every field
    return {
        name: np.asarray(arr).tolist() for name, arr in zip(outs._fields, outs)
    }


def toolchain() -> dict:
    return {
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "jax_threefry_partitionable": bool(
            jax.config.jax_threefry_partitionable
        ),
        "jax_default_prng_impl": str(jax.config.jax_default_prng_impl),
        "backend": jax.default_backend(),
    }


def main() -> None:
    graph = random_regular_graph(N, DEG, seed=GRAPH_SEED)
    payload = {"toolchain": toolchain(), "ensemble": {}, "sweep": {}}
    for name, pcfg, fcfg in cases():
        outs = Experiment(
            graph=graph, protocol=pcfg, failures=fcfg, steps=STEPS,
            outputs="full",
        ).ensemble(SEEDS, base_key=BASE_KEY)
        payload["ensemble"][name] = _outputs_to_dict(outs)

    sweep_cases = [
        (_pcfg("decafork", eps=e), f)
        for e, f in zip((1.4, 2.2), (FailureConfig(burst_times=(20,), burst_sizes=(2,)),
                                     FailureConfig(burst_times=(30,), burst_sizes=(1,), p_fail=0.002)))
    ]
    outs = Experiment(
        graph=graph, scenarios=sweep_cases, steps=STEPS, outputs="full",
    ).plan().sweep_stacked(seeds=SEEDS, base_key=BASE_KEY)
    payload["sweep"]["decafork/eps-grid"] = _outputs_to_dict(outs)

    with open(OUT, "w") as f:
        json.dump(payload, f)
    print(f"wrote {OUT}")


if __name__ == "__main__":
    main()
