"""Readings that set a cell's limits: the control and the planted faults.

    python3 chipbench/control.py --workload <cell> --seeds 11,12,13 --studies 2
    python3 chipbench/control.py --workload <cell> --seeds 11,12,13 --fault unchanged_state --seconds 1
    python3 chipbench/control.py --workload <cell> --seeds 1,2,...,12 --sound

Without ``--fault`` the control takes the program's place: for the
studies a run with each seed would submit (``--studies`` of them) and the
trajectories its check would sample, the reference one precision step
down is compared with the reference itself, and the numbers the check
compares are printed, one JSON line per seed and control (for a learning
payload two controls: everything in bfloat16, and the model alone). With
``--fault`` whole runs of the cell are made with that fault planted in
the program (``faults.py``), one per seed, and their compared numbers are
printed the same way. ``--sound`` reads the unbroken program the same way
on many seeds in one process: the plans are built once, and each seed's
first study goes through the timed path and the run's check. Limits lie
between the largest reading of sound runs and the smallest of the
others. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def control_readings(cell, seed: int, n_studies: int) -> dict:
    """``{control: numbers}`` for each control of the cell."""
    import itertools

    from chipbench import check, graphs, reference, studies

    cfg, kind = cell.config, cell.kind
    neighbors = graphs.make(cfg["graph"])
    inputs = kind.inputs(cfg["payload"]) if kind else None
    controls = ("bfloat16", "bfloat16-payload") if kind else ("bfloat16",)
    done = list(itertools.islice(studies.studies(cell.traffic, seed), n_studies))
    nums = {c: [] for c in controls}
    for st, i in studies.sample(cell.traffic, seed, done):
        ref = reference.replay(cfg, neighbors, st, i, kind, inputs)
        for c in controls:
            low = reference.replay(cfg, neighbors, st, i, kind, inputs, precision=c)
            nums[c].append(check.compare_trajectory(low, ref))
    return {f"control.{c}": check.combine(n) for c, n in nums.items()}


def sound_readings(cell, seeds):
    """``(seed, numbers)`` of the unbroken program, one study per seed."""
    from chipbench import graphs, run, studies
    from chipbench.program import Program

    cfg, traffic = cell.config, cell.traffic
    neighbors = graphs.make(cfg["graph"])
    inputs = cell.kind.inputs(cfg["payload"]) if cell.kind else None
    protocols = list(dict.fromkeys(s["protocol"] for s in traffic["studies"]))
    program = Program(cfg, neighbors, protocols, cell.kind, inputs)
    for seed in seeds:
        st = next(studies.studies(traffic, seed))
        done = [(st, program.fetch(program.dispatch(st)))]
        yield seed, run.judge(cell, seed, done, neighbors, inputs)[0]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--studies", type=int, default=2)
    ap.add_argument("--fault", default=None)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--sound", action="store_true")
    args = ap.parse_args(argv)
    for p in (str(ROOT), str(ROOT / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from chipbench import faults, run, spec

    run.use_cache()
    import jax

    cell = spec.resolve(args.workload)
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("control: no TPU")
    seeds = [int(s) for s in args.seeds.split(",")]

    def show(seed, mode, nums, t):
        print(json.dumps({
            "workload": cell.name, "seed": seed, "mode": mode, "numbers": nums,
            "seconds": time.perf_counter() - t,
        }), flush=True)

    t = time.perf_counter()
    if args.sound:
        for seed, nums in sound_readings(cell, seeds):
            show(seed, "sound", nums, t)
            t = time.perf_counter()
        return 0
    with faults.planted(cell.kind, args.fault):
        for seed in seeds:
            t = time.perf_counter()
            if args.fault is None:
                for mode, nums in control_readings(cell, seed, args.studies).items():
                    show(seed, mode, nums, t)
            else:
                checks = run.run(cell, seed, args.seconds, False, t0=t)["checks"]
                show(seed, args.fault, {k: c["value"] for k, c in checks.items()}, t)
    return 0


if __name__ == "__main__":
    sys.exit(main())
