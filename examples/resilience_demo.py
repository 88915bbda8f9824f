"""Resilience demo: reproduce Fig. 1 as an ASCII time series.

Runs MISSINGPERSON / DECAFORK / DECAFORK+ through two burst failures and
plots Z_t in the terminal — the fastest way to *see* the paper's claim.

Run:  PYTHONPATH=src python examples/resilience_demo.py [--full]
"""
import argparse

import numpy as np

from repro.api import Experiment
from repro.core import FailureConfig, ProtocolConfig
from repro.graphs import random_regular_graph
from repro.utils.compile_cache import use_compile_cache


def ascii_plot(z, z0, width=100, height=12, title=""):
    z = np.asarray(z, float)
    idx = np.linspace(0, len(z) - 1, width).astype(int)
    zz = z[idx]
    top = max(zz.max(), z0 * 2)
    rows = []
    for level in np.linspace(top, 0, height):
        line = "".join("#" if v >= level > v - top / height else
                       ("-" if abs(level - z0) < top / height / 2 else " ")
                       for v in zz)
        rows.append(f"{level:5.1f} |{line}")
    print(f"\n{title}  (- marks Z0={z0})")
    print("\n".join(rows))
    print("      +" + "-" * width)


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper-scale run")
    args = ap.parse_args()

    n, z0 = (100, 10) if args.full else (64, 8)
    steps = 9000 if args.full else 3000
    bursts = (2000, 6000) if args.full else (1000, 2000)
    proto_start = 1000 if args.full else 500

    g = random_regular_graph(n, 8, seed=0)
    fcfg = FailureConfig(burst_times=bursts, burst_sizes=(z0 // 2, z0 // 2 + 1))
    cases = [
        ("MISSINGPERSON (eps_mp=400)", "missingperson", dict(eps_mp=400.0)),
        ("DECAFORK (eps=2)", "decafork", dict(eps=2.0)),
        ("DECAFORK+ (eps=3, eps2=7.57)", "decafork+", dict(eps=3.0, eps2=7.57)),
    ]
    for title, alg, kw in cases:
        pcfg = ProtocolConfig(
            algorithm=alg, z0=z0, max_walks=64, protocol_start=proto_start, **kw
        )
        _, outs = Experiment(
            graph=g, protocol=pcfg, failures=fcfg, steps=steps
        ).run(key=0)
        z = np.asarray(outs.z)
        ascii_plot(z, z0, title=title)
        print(f"   forks={int(np.asarray(outs.forks).sum())} "
              f"terms={int(np.asarray(outs.terms).sum())} "
              f"maxZ={z.max()} survived={(z > 0).all()}")


if __name__ == "__main__":
    main()
