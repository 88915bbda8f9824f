"""Smoke run of the DecAFork main path on a TPU, through the user API.

    python chip_smoke.py              # one chip: every phase below
    python chip_smoke.py --chips 4    # four chips: the sharded sweep only

One process drives the chip. Phases (any failure exits non-zero):

  walks     the paper's own scale (benchmarks/common.py at BENCH_FULL:
            100-node 8-regular graph, Z0=10, 64 walk slots, 1024
            return-time bins, 9000 steps, 50 seeds, bursts of 5 and 6
            walks at t=2000 and t=6000) for DECAFORK and DECAFORK+ via
            ``Experiment.ensemble``. The round decision must be the
            whole-round Pallas kernel, and the integer control plane
            (z, forks, terms, failures) must be bitwise the unfused stage
            sequence run on the same chip;
  payload   RW-SGD with the paper_rwsgd model at its own widths (4 layers,
            d_model 256, d_ff 1024, vocab 4096) riding 16 walk slots: the
            loss stays finite and falls, and the walk trajectory is
            bitwise the payload-free run;
  durable   a segmented ``sweep_stacked`` with a ResultStore, killed at a
            chunk boundary and resumed: bitwise the monolithic sweep, with
            the snapshot written and read back (no silent store miss);
  service   two callers submit through ``ExperimentService``; each result
            is bitwise the direct sweep.

With ``--chips 4`` only a scenario sweep with ``placement="sharded"``
runs, compared bitwise with ``placement="local"``; its outputs must be
split across the four devices.

Times printed here are smoke timings of single runs, not benchmark
numbers. The last line of stdout is the JSON verdict
``{"ok": true, "device": {...}}``; nothing is printed there unless every
phase passed. Without a TPU the script exits non-zero before any work.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import tempfile
import threading
import time
import warnings

ROOT = os.path.dirname(os.path.abspath(__file__))
CONTROL_PLANE = ("z", "forks", "terms", "failures")


class SmokeFailure(AssertionError):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


class CompileClock:
    """Seconds of XLA compilation (JAX's own backend-compile duration
    events) since the last ``take``."""

    def __init__(self):
        import jax

        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.total += duration

    def take(self) -> float:
        total, self.total = self.total, 0.0
        return total


def timed(clock, label, fn):
    """Run ``fn`` to completion; print its compile and remaining seconds."""
    import jax

    clock.take()
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    wall = time.perf_counter() - t0
    comp = clock.take()
    print(
        f"  smoke timing {label}: wall {wall:.2f}s = compile {comp:.2f}s"
        f" + run {wall - comp:.2f}s"
    )
    return out


def bitwise_equal(a, b) -> bool:
    import jax
    import numpy as np

    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb)
    )


def first_divergence(got, want, fields):
    """``(seed, round)`` of the earliest mismatch over ``fields`` of two
    ``(seeds, steps)`` recorded trajectories, or None."""
    import numpy as np

    first = None
    for f in fields:
        diff = np.asarray(getattr(got, f)) != np.asarray(getattr(want, f))
        for s, t in zip(*np.nonzero(diff)):
            if first is None or t < first[1]:
                first = (int(s), int(t))
    return first


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def walks_phase(clock, graph, pcfgs, fcfg, steps, seeds, backend="pallas"):
    """Paper-scale ensembles: fused kernel vs the unfused stage sequence."""
    import numpy as np

    from repro.api import Experiment

    for pcfg in pcfgs:
        alg = pcfg.algorithm
        exp = Experiment(graph=graph, protocol=pcfg, failures=fcfg, steps=steps)
        plan = exp.plan()
        (_, _, dec), = plan.round_decisions()
        print(f"[walks] {alg}: round decision {dec.impl}/{dec.backend} ({dec.reason})")
        check(
            dec.impl == "fused" and dec.backend == backend,
            f"{alg} does not reach the whole-round kernel: {dec}",
        )
        fused = timed(
            clock, f"{alg} fused ensemble ({seeds} seeds x {steps} steps)",
            lambda: plan.ensemble(seeds),
        )
        ref_plan = Experiment(
            graph=graph,
            protocol=dataclasses.replace(pcfg, round_impl="unfused"),
            failures=fcfg,
            steps=steps,
        ).plan()
        (_, _, ref_dec), = ref_plan.round_decisions()
        check(not ref_dec.fused, f"oracle plan is not unfused: {ref_dec}")
        unfused = timed(
            clock, f"{alg} unfused ensemble", lambda: ref_plan.ensemble(seeds)
        )
        where = first_divergence(fused, unfused, CONTROL_PLANE)
        if where is not None:
            s, t = where
            lo = max(t - 2, 0)
            for f in CONTROL_PLANE + ("theta_mean",):
                print(
                    f"  seed {s} rounds {lo}..{t} {f}: fused "
                    f"{getattr(fused, f)[s, lo:t + 1].tolist()} unfused "
                    f"{getattr(unfused, f)[s, lo:t + 1].tolist()}"
                )
            raise SmokeFailure(
                f"{alg}: fused and unfused control planes differ first at "
                f"seed {s}, round {t}"
            )
        # theta_mean is an f32 mean (>= 0, so the int32 views are ordered):
        # report how far the two paths' rounding apart, without gating on it
        ulps = np.abs(
            np.asarray(fused.theta_mean).view(np.int32).astype(np.int64)
            - np.asarray(unfused.theta_mean).view(np.int32)
        )
        z = fused.z
        print(
            f"[walks] {alg}: fused == unfused bitwise on "
            f"{'/'.join(CONTROL_PLANE)}; theta_mean differs in "
            f"{int((ulps > 0).sum())} of {ulps.size} (seed, round) entries, "
            f"by at most {int(ulps.max())} ulp; z mean {float(z.mean()):.3f}, "
            f"min {int(z.min())}, max {int(z.max())}; forks "
            f"{int(fused.forks.sum())}, terms {int(fused.terms.sum())}, "
            f"failures {int(fused.failures.sum())}"
        )


def payload_phase(clock, graph, model_cfg, steps, burst_at, burst_size):
    """RW-SGD at the config's own widths: finite, falling loss; z neutral."""
    import numpy as np

    from repro.api import Experiment
    from repro.core import FailureConfig, ProtocolConfig
    from repro.data import make_markov_task
    from repro.models.model import Model
    from repro.optim import RwSgdPayload, adamw

    max_walks = 16
    pcfg = ProtocolConfig(
        algorithm="decafork", z0=6, max_walks=max_walks, eps=1.2,
        protocol_start=steps // 4, rt_bins=512,
    )
    fcfg = FailureConfig(burst_times=(burst_at,), burst_sizes=(burst_size,))
    payload = RwSgdPayload(
        Model(model_cfg), adamw(3e-3), make_markov_task(model_cfg.vocab_size),
        max_walks=max_walks, local_batch=2, seq_len=32,
    )
    print(
        f"[payload] {model_cfg.name}: {model_cfg.num_layers} layers, d_model "
        f"{model_cfg.d_model}, d_ff {model_cfg.d_ff}, vocab "
        f"{model_cfg.vocab_size}; {max_walks} slots, {steps} steps"
    )
    (_, _), (outs, learn) = timed(
        clock, "payload run",
        lambda: Experiment(
            graph=graph, protocol=pcfg, failures=fcfg, steps=steps,
            payload=payload,
        ).run(key=0),
    )
    _, bare = timed(
        clock, "payload-free run",
        lambda: Experiment(
            graph=graph, protocol=pcfg, failures=fcfg, steps=steps
        ).run(key=0),
    )
    loss = np.asarray(learn.mean_loss)
    trained = np.asarray(learn.trained) > 0
    check(np.isfinite(loss).all(), "payload loss is not finite")
    w = max(steps // 10, 1)
    start = loss[:w][trained[:w]].mean()
    end = loss[-w:][trained[-w:]].mean()
    print(f"[payload] mean loss first {w} rounds {start:.4f} -> last {w} {end:.4f}")
    check(end < start, f"loss did not fall: {start} -> {end}")
    check(
        bitwise_equal(outs.z, bare.z),
        "the payload changed the walk trajectory (z)",
    )
    print("[payload] z bitwise equal with and without the payload")


def durable_phase(clock, graph, scenarios, steps, seeds, segment_steps, root):
    """Kill a segmented sweep at a chunk boundary, resume, compare."""
    from repro.api import Experiment
    from repro.api.store import ResultStore
    from repro.utils.faults import FaultPlan, Kill, SimulatedKill

    plan = Experiment(graph=graph, scenarios=scenarios, steps=steps).plan()
    mono = timed(
        clock, "monolithic sweep",
        lambda: plan.sweep_stacked(seeds=seeds),
    )
    store = ResultStore(os.path.join(root, "store"))
    faults = FaultPlan().skip("segment.boundary", 1).at(
        "segment.boundary", Kill()
    )
    # a failed snapshot write must fail the phase, not degrade to lost
    # progress: the write-behind's best-effort warning becomes an error
    with warnings.catch_warnings():
        warnings.filterwarnings("error", message="segment write-behind failed")
        try:
            with faults.active():
                plan.sweep_stacked(
                    seeds=seeds, store=store, segment_steps=segment_steps
                )
        except SimulatedKill:
            pass
        else:
            raise SmokeFailure("the scripted kill did not fire")
        check(store.puts >= 1, "no segment snapshot was written before the kill")
        print(
            f"[durable] killed after {2 * segment_steps}/{steps} steps; "
            f"snapshots written: {store.puts}"
        )
        resumed = timed(
            clock, "resumed sweep",
            lambda: plan.sweep_stacked(
                seeds=seeds, store=store, segment_steps=segment_steps
            ),
        )
        # an unreadable snapshot would count as a miss and rerun from 0;
        # the only misses allowed are the two lookups of the final result
        # made before it existed
        check(store.hits == 1, f"the resume read no snapshot: {store!r}")
        check(store.misses == 2, f"unreadable store entries: {store!r}")
        check(bitwise_equal(resumed, mono), "resumed sweep != monolithic sweep")
        warm = plan.sweep_stacked(seeds=seeds, store=store)
        check(store.hits == 2, f"the stored result was not read back: {store!r}")
        check(bitwise_equal(warm, mono), "stored result != monolithic sweep")
    print(f"[durable] resumed == stored == monolithic, bitwise ({store!r})")


def service_phase(clock, graph, scenarios, steps, seeds):
    """Two concurrent callers through the coalescing service."""
    from repro.api import Experiment
    from repro.api.service import ExperimentService

    exp = Experiment(graph=graph, scenarios=scenarios, steps=steps)
    halves = [scenarios[: len(scenarios) // 2], scenarios[len(scenarios) // 2:]]
    futures = [None, None]
    with ExperimentService(exp, store=None, linger=0.2) as svc:

        def caller(i):
            futures[i] = svc.submit(halves[i], seeds=seeds)

        def both():
            threads = [threading.Thread(target=caller, args=(i,)) for i in (0, 1)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            return [dict(f.result(timeout=900).items()) for f in futures]

        results = timed(clock, "service, two callers", both)
        stats = dict(svc.stats)
    direct = timed(
        clock, "direct sweep", lambda: dict(exp.sweep(seeds=seeds).items())
    )
    for half, res in zip(halves, results):
        for s in half:
            check(
                bitwise_equal(res[s.name], direct[s.name]),
                f"service result for {s.name} != direct sweep",
            )
    print(f"[service] both callers == direct sweep, bitwise; stats {stats}")


def sharded_phase(clock, graph, scenarios, steps, seeds, n_devices):
    """Scenario axis over ``n_devices`` chips vs the same sweep local."""
    from repro.api import Experiment

    def sweep(placement):
        return Experiment(
            graph=graph, scenarios=scenarios, steps=steps, placement=placement
        ).plan().sweep_stacked(seeds=seeds)

    sharded = timed(clock, "sharded sweep", lambda: sweep("sharded"))
    local = timed(clock, "local sweep", lambda: sweep("local"))
    for name, leaf in zip(sharded._fields, sharded):
        sh = leaf.sharding
        print(f"[sharded] {name}: {len(sh.device_set)} devices, {sh}")
        check(
            len(sh.device_set) == n_devices,
            f"{name} does not span {n_devices} devices: {sh}",
        )
    # z is computed per scenario row; a replicated z means every chip ran
    # every scenario (a field that is constant, such as DECAFORK's terms,
    # may legitimately come back replicated)
    check(
        not sharded.z.sharding.is_fully_replicated,
        f"z is replicated, not split by scenario: {sharded.z.sharding}",
    )
    check(bitwise_equal(sharded, local), "sharded sweep != local sweep")
    print(f"[sharded] {len(scenarios)} scenarios over {n_devices} chips == local, bitwise")


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


def eps_scenarios(count):
    """DECAFORK rows over an eps grid, paper protocol and bursts."""
    from benchmarks import common
    from repro.sweep import Scenario

    return [
        Scenario(
            f"eps{eps:.2f}",
            common.pcfg_for("decafork", eps=eps),
            common.burst_failures(),
        )
        for eps in [1.9 + 0.1 * i for i in range(count)]
    ]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4: run only the sharded scenario sweep across four chips",
    )
    args = ap.parse_args(argv)
    for var in ("REPRO_ROUND_IMPL", "REPRO_ESTIMATOR_IMPL"):
        if os.environ.get(var):
            sys.exit(f"chip_smoke: {var} is set; unset it so the defaults run")

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(
            f"chip_smoke: no TPU; JAX sees {devices[0].platform} devices only"
        )
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but {len(devices)} device(s)")

    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    # the paper-scale constants of benchmarks/common.py
    os.environ["BENCH_FULL"] = "1"
    from repro.utils.compile_cache import use_compile_cache

    print(f"device: {devices[0].device_kind} x{len(devices)}; jax {jax.__version__}")
    print(f"compile cache: {use_compile_cache()}")

    from benchmarks import common
    from repro.configs import paper_rwsgd
    from repro.graphs import make_graph

    check(common.FULL and common.STEPS == 9000, "BENCH_FULL constants not loaded")
    clock = CompileClock()
    graph = make_graph("regular", 100, seed=0, degree=8)
    t0 = time.perf_counter()
    if args.chips == 4:
        sharded_phase(clock, graph, eps_scenarios(8), 2000, 8, len(devices))
    else:
        walks_phase(
            clock, graph,
            [common.pcfg_for("decafork"), common.pcfg_for("decafork+")],
            common.burst_failures(), common.STEPS, common.SEEDS,
        )
        payload_phase(
            clock, make_graph("regular", 64, seed=0, degree=8),
            paper_rwsgd.CONFIG, steps=400, burst_at=300, burst_size=3,
        )
        with tempfile.TemporaryDirectory(prefix="chip_smoke_") as root:
            durable_phase(
                clock, graph, eps_scenarios(2), steps=3000, seeds=8,
                segment_steps=1000, root=root,
            )
        service_phase(clock, graph, eps_scenarios(4), steps=2000, seeds=8)
    print(f"all phases passed in {time.perf_counter() - t0:.1f}s (smoke timing)")
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
