"""Experiment service demo: many callers, coalesced compiled calls.

Three "users" each submit their own scenario list against one shared
Experiment. Scenarios that share static structure (and seeds/base key)
coalesce into ONE ``sweep_stacked`` call — the service stats show fewer
compiled batches than submissions — and results stream back per group.
With ``REPRO_RESULT_STORE`` set (or ``--store DIR``), a second run of
this script answers every submission from disk without compiling
anything.

Run:  PYTHONPATH=src python examples/experiment_service_demo.py [--store DIR]
"""
import argparse

import numpy as np

from repro.api import Experiment, ExperimentService
from repro.core.failures import FailureConfig
from repro.core.protocol import ProtocolConfig
from repro.sweep import Scenario
from repro.utils.compile_cache import use_compile_cache


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--store", default="env",
                    help="result-store dir ('env': honor $REPRO_RESULT_STORE)")
    ap.add_argument("--n", type=int, default=64)
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--seeds", type=int, default=8)
    args = ap.parse_args()

    # one registered, config-driven study shared by every caller
    exp = Experiment.from_config({
        "experiment": "walks",
        "graph": "regular",
        "n": args.n,
        "steps": args.steps,
        "outputs": "scalars",
    })

    def scen(name, eps, bursts=()):
        return Scenario(
            name,
            ProtocolConfig(eps=eps),
            FailureConfig(burst_times=bursts, burst_sizes=(2,) * len(bursts)),
        )

    with ExperimentService(exp, store=args.store, autostart=False) as svc:
        # three callers, five scenarios, ONE static structure -> 1 batch
        f1 = svc.submit([scen("a/eps=1.8", 1.8), scen("a/eps=2.0", 2.0)],
                        seeds=args.seeds)
        f2 = svc.submit([scen("b/eps=2.2", 2.2)], seeds=args.seeds)
        f3 = svc.submit([scen("c/burst", 2.0, bursts=(100,)),
                         scen("c/calm", 2.0)], seeds=args.seeds)
        svc.flush()

        for fut in (f1, f2, f3):
            for name, outs, _ in fut.stream():
                z_final = float(np.mean(np.asarray(outs.z)[:, -1]))
                print(f"  {name:12s} mean final walk count = {z_final:.2f}")
        s = svc.stats
        print(
            f"{s['submissions']} submissions / {s['scenarios']} scenarios "
            f"ran as {s['batches']} compiled batch(es)"
        )
        if svc.store is not None:
            print(f"store: {svc.store!r}")


if __name__ == "__main__":
    main()
