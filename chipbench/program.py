"""The system under test, driven through its normal entry.

It turns a configuration into ``Experiment(...).plan()`` objects and runs
a study as ``Plan.ensemble``. Beside it, only a payload kind's builder
(``payloads/<kind>.py``, which makes the program's payload object) and the
planted faults (``faults.py``) import ``repro``. Inputs handed over (the
graph, the learning task) are made by the benchmark, not taken from the
program.
"""
from __future__ import annotations

import jax
import numpy as np

from chipbench import spec


class Program:
    """One ``Plan`` per protocol the traffic submits, built once; with a
    learning payload, ``kind`` is the configuration's payload kind
    (``payloads.of``) and ``inputs`` what its ``inputs`` made."""

    def __init__(self, config: dict, neighbors: np.ndarray, protocols, kind=None, inputs=None):
        from repro.api import Experiment
        from repro.core import FailureConfig, ProtocolConfig
        from repro.graphs.generators import Graph

        graph = Graph(
            n=int(neighbors.shape[0]),
            neighbors=neighbors,
            degrees=np.full(neighbors.shape[0], neighbors.shape[1], np.int32),
            family=config["graph"]["family"],
        )
        f = config["failures"]
        failures = FailureConfig(
            burst_times=tuple(f["burst_times"]), burst_sizes=tuple(f["burst_sizes"])
        )
        self.payload = kind.build(config, inputs) if kind is not None else None
        self.plans = {
            name: Experiment(
                graph=graph,
                protocol=ProtocolConfig(**spec.protocol(config, name)),
                failures=failures,
                steps=config["steps"],
                payload=self.payload,
            ).plan()
            for name in protocols
        }

    def dispatch(self, study):
        """Start one study; returns its outputs as device arrays."""
        return self.plans[study.protocol].ensemble(study.seeds, base_key=study.base_key)

    def compiled_bytes(self, study) -> dict:
        """Bytes the compiled program of ``study`` holds on the device:
        its arguments, outputs and temporaries (``memory_analysis`` of
        the executable ``Plan.ensemble`` runs, lowered with the same
        arguments; the normal entry does not hand the executable out)."""
        from repro.api import plan as plan_mod

        p = self.plans[study.protocol]
        pcfg, fcfg = p._require_base("ensemble")
        keys = jax.random.split(jax.random.key(study.base_key), study.seeds)
        sig = p._signature("ensemble", pcfg, plan_mod._schedule_lens(fcfg), fcfg)
        m = plan_mod.executable("ensemble", sig).lower(
            keys, p.neighbors, p.degrees, p.mirror, p._pi(pcfg), pcfg, fcfg,
            steps=p.steps, n=p.n, payload=p.payload, spec=p.spec, pspec=p.pspec,
        ).compile().memory_analysis()
        return {
            "argument": int(m.argument_size_in_bytes),
            "output": int(m.output_size_in_bytes),
            "temp": int(m.temp_size_in_bytes),
        }

    def fetch(self, outs) -> dict:
        """The study's outputs on the host, by field name."""
        host = jax.device_get(outs)
        if self.payload is None:
            rec, pay = host, None
        else:
            rec, pay = host
        fields = {f: np.asarray(getattr(rec, f)) for f in rec._fields}
        if pay is not None:
            fields.update(loss=np.asarray(pay.loss), trained=np.asarray(pay.trained))
        return fields

