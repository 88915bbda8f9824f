"""Benchmark harness entry point — one benchmark per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. Reduced sizes by default;
set BENCH_FULL=1 for the paper-scale ensembles (50 seeds, 9000 steps).

  PYTHONPATH=src python -m benchmarks.run             # all figures
  PYTHONPATH=src python -m benchmarks.run fig1 fig3   # a subset
  PYTHONPATH=src python -m benchmarks.run --smoke     # seconds-fast CI lane

``--smoke`` runs no timings: it asserts the estimator implementations
(gather / compare / pallas / fused, jnp AND interpret-mode kernels) agree
on tiny shapes — the drift tripwire for every PR's fast CI lane.
"""
from __future__ import annotations

import sys
import time

from benchmarks import (
    auto_eps,
    bench_payload,
    bench_resume,
    bench_round,
    bench_service,
    bench_sweep,
    fig1_burst,
    fig2_probabilistic,
    fig3_byzantine,
    fig4_nodes,
    fig5_epsilon,
    fig6_graphs,
    fig7_topology,
    fig8_learning,
    fig9_zoo,
    kernel_theta,
    theory_bounds,
)
from repro.utils.compile_cache import use_compile_cache

BENCHES = {
    "fig1": fig1_burst.run,
    "fig2": fig2_probabilistic.run,
    "fig3": fig3_byzantine.run,
    "fig4": fig4_nodes.run,
    "fig5": fig5_epsilon.run,
    "fig6": fig6_graphs.run,
    "fig7": fig7_topology.run,
    "fig8": fig8_learning.run,
    "fig9": fig9_zoo.run,
    "theory": theory_bounds.run,
    "kernel_theta": kernel_theta.run,
    "auto_eps": auto_eps.run,
    "sweep": bench_sweep.run,
    "round": bench_round.run,
    "payload": bench_payload.run,
    "service": bench_service.run,
    "resume": bench_resume.run,
}


def smoke() -> None:
    """Estimator-impl + API agreement tripwire (tiny shapes, no timing).

    Asserts, in a few seconds:
      * one fused observation round (ref AND interpret-mode Pallas
        round_update, AND the theta_survival kernel) is bitwise the
        unfused gather/compare sequence, on a non-tile-multiple n;
      * a short simulation drives the same trajectory under every
        estimator_impl (gather vs compare/pallas/fused decisions may
        round differently in float, so trajectories are compared within
        the node-sum family and the gather family separately);
      * the whole-round fused path (``round_impl="fused"``) is bitwise
        the literal unfused stage sequence over a full churny
        trajectory — every recorded output, not just z;
      * the legacy runner shims (run_simulation / run_ensemble /
        run_sweep / run_scenarios) are bitwise the new Experiment API —
        the deprecation layer must never drift from the real path.
    """
    import dataclasses
    import warnings

    import jax
    import numpy as np

    from repro.api import Experiment
    from repro.core import FailureConfig, ProtocolConfig
    from repro.core import estimator as est
    from repro.graphs import random_regular_graph
    from repro.kernels import (
        round_update_pallas,
        round_update_ref,
        theta_sums_pallas,
    )
    from repro.kernels.round_update import random_round_inputs
    from repro.utils.deprecation import APIDeprecationWarning

    # --- one-round bitwise agreement on an odd n ------------------------
    args = random_round_inputs(jax.random.key(7), 13, 6, 32, 6)
    ls, hist, total, pos, track, r, valid, upd, t = args
    want = round_update_ref(*args)
    got = round_update_pallas(*args, interpret=True)
    for name, a, b in zip(("last_seen", "hist", "total", "sums"), want, got):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b), err_msg=f"round_update: {name}"
        )
    sums_kernel = theta_sums_pallas(want[0], want[1], want[2], t, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(sums_kernel), np.asarray(want[3]), err_msg="theta_sums"
    )
    cum = est.survival_cumulative(est.ReturnTimeState(want[1], want[2]))
    theta_g = est.theta_hat(want[0], cum, want[2], t, pos, track)
    theta_r = est.theta_hat_rows(want[0], want[1], want[2], t, pos, track)
    np.testing.assert_array_equal(
        np.asarray(theta_g), np.asarray(theta_r), err_msg="theta rows"
    )
    # node-sum theta assumes the walk's own column was just stamped with t,
    # which only holds for ACTIVE walks (exactly where the protocol reads it)
    act = np.asarray(upd) >= 0
    np.testing.assert_allclose(
        np.asarray(theta_g)[act],
        np.asarray(est.theta_hat_from_node_sums(want[3], pos))[act],
        rtol=1e-5, atol=1e-5, err_msg="gather vs node sums",
    )

    # --- trajectory agreement across estimator_impl ---------------------
    g = random_regular_graph(19, 4, seed=2)
    fcfg = FailureConfig(burst_times=(30,), burst_sizes=(2,))
    zs = {}
    for impl in ("gather", "compare", "pallas", "fused", "auto"):
        pcfg = ProtocolConfig(
            algorithm="decafork", z0=4, max_walks=8, eps=1.4,
            protocol_start=15, rt_bins=32, estimator_impl=impl,
        )
        _, o = Experiment(
            graph=g, protocol=pcfg, failures=fcfg, steps=60
        ).run(key=5)
        zs[impl] = np.asarray(o.z)
    for impl in ("pallas", "fused"):
        np.testing.assert_array_equal(
            zs[impl], zs["compare"], err_msg=f"{impl} vs compare trajectory"
        )
    # 'auto' must resolve to the backend's best impl's exact trajectory
    auto_family = "fused" if jax.default_backend() == "tpu" else "gather"
    np.testing.assert_array_equal(
        zs["auto"], zs[auto_family],
        err_msg=f"auto vs {auto_family} trajectory",
    )

    # --- whole-round fusion vs the unfused oracle ------------------------
    # the fused round must reproduce the literal stage sequence bitwise on
    # every recorded output, under node/link churn and a burst
    churn = FailureConfig(
        burst_times=(30,), burst_sizes=(2,),
        p_node_fail=0.02, p_node_recover=0.3, node_fail_start=10,
        p_link_fail=0.05, p_link_recover=0.4, link_fail_start=10,
    )
    outs = {}
    for rimpl in ("fused", "unfused"):
        pcfg = ProtocolConfig(
            algorithm="decafork+", z0=4, max_walks=8, eps=1.4, eps2=6.0,
            protocol_start=15, rt_bins=32, estimator_impl="gather",
            round_impl=rimpl,
        )
        _, outs[rimpl] = Experiment(
            graph=g, protocol=pcfg, failures=churn, steps=60,
            outputs="full",
        ).run(key=5)
    for name, a, b in zip(outs["fused"]._fields, outs["fused"],
                          outs["unfused"]):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b),
            err_msg=f"whole-round fused vs unfused: {name}",
        )

    # --- new API vs legacy-shim bitwise agreement ------------------------
    from repro.core import run_ensemble, run_simulation
    from repro.core.simulator import run_sweep
    from repro.sweep import Scenario, run_scenarios

    pcfg = ProtocolConfig(
        algorithm="decafork", z0=4, max_walks=8, eps=1.6,
        protocol_start=15, rt_bins=32,
    )
    pcfg2 = ProtocolConfig(
        algorithm="missingperson", z0=4, max_walks=8, eps_mp=20.0,
        protocol_start=15, rt_bins=32,
    )
    scen = [Scenario("dfk", pcfg, fcfg), Scenario("mp", pcfg2, fcfg)]
    exp = Experiment(graph=g, protocol=pcfg, failures=fcfg, steps=60,
                     outputs="full", scenarios=scen)
    plan = exp.plan()
    _, new_run = plan.run(key=5)
    new_ens = plan.ensemble(2, base_key=5)
    new_stack = plan.sweep_stacked([scen[0]], seeds=2, base_key=5)
    new_mixed = plan.sweep(seeds=2, base_key=5)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", APIDeprecationWarning)
        _, old_run = run_simulation(g, pcfg, fcfg, steps=60, key=5,
                                    outputs="full")
        old_ens = run_ensemble(g, pcfg, fcfg, steps=60, seeds=2, base_key=5,
                               outputs="full")
        old_stack = run_sweep(g, [scen[0]], steps=60, seeds=2, base_key=5,
                              outputs="full")
        old_mixed = run_scenarios(g, scen, steps=60, seeds=2, base_key=5,
                                  outputs="full")
    for label, a, b in (
        ("run_simulation", new_run, old_run),
        ("run_ensemble", new_ens, old_ens),
        ("run_sweep", new_stack, old_stack),
    ):
        for name, x, y in zip(a._fields, a, b):
            np.testing.assert_array_equal(
                np.asarray(x), np.asarray(y),
                err_msg=f"shim drift: {label}.{name}",
            )
    assert old_mixed.names == new_mixed.names
    for name in new_mixed.names:
        for f, x, y in zip(new_mixed[name]._fields, new_mixed[name],
                           old_mixed[name]):
            np.testing.assert_array_equal(
                np.asarray(x), np.asarray(y),
                err_msg=f"shim drift: run_scenarios[{name}].{f}",
            )

    # --- zoo default-variant bitwise tripwire ----------------------------
    # the zoo's neutral row — uniform defense, every zoo knob explicit at
    # its neutral value — must be bitwise the plain config: the variant
    # dispatch and the attack machinery cost the default program nothing
    from repro.zoo import defense, zoo_scenarios

    plain_p = ProtocolConfig(
        algorithm="decafork", z0=4, max_walks=8, eps=1.4,
        protocol_start=15, rt_bins=32,
    )
    zoo_p = dataclasses.replace(
        plain_p, **defense("uniform"),
        walk_variant="uniform", p_jump=0.0, bias_p=1.0, bias_q=1.0,
    )
    zoo_f = dataclasses.replace(
        churn, pacman_nodes=(), pacman_mobile=False,
        edge_cut_times=(), edge_cut_thresholds=(),
    )
    plain_out = Experiment(
        graph=g, protocol=plain_p, failures=churn, steps=60, outputs="full"
    ).ensemble(2, base_key=5)
    zoo_out = Experiment(
        graph=g, protocol=zoo_p, failures=zoo_f, steps=60, outputs="full"
    ).ensemble(2, base_key=5)
    for name, a, b in zip(zoo_out._fields, zoo_out, plain_out):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b),
            err_msg=f"zoo neutral row drift: {name}",
        )
    # and the grid helper stays wired: a defense|attack row is buildable
    # and carries the expected statics
    [row] = zoo_scenarios(["jump"], [("edge_cut", {"time": 30, "threshold": 9})],
                          base_protocol=plain_p)
    assert row.pcfg.walk_variant == "jump" and row.fcfg.n_edge_cuts == 1

    # --- service coalescing bitwise tripwire -----------------------------
    # two callers sharing one static structure coalesce into one batch,
    # and each caller's rows stay bitwise what a private sweep returns
    from repro.api import ExperimentService

    s_a = Scenario("svc_a", pcfg, fcfg)
    s_b = Scenario("svc_b", dataclasses.replace(pcfg, eps=1.9), fcfg)
    with ExperimentService(plan, store=None, autostart=False) as svc:
        fa = svc.submit([s_a], seeds=2, base_key=5)
        fb = svc.submit([s_b], seeds=2, base_key=5)
        svc.flush()
        assert svc.stats["batches"] == 1, svc.stats
        coalesced = {"svc_a": fa.result()["svc_a"], "svc_b": fb.result()["svc_b"]}
    seq = plan.sweep([s_a, s_b], seeds=2, base_key=5)
    for name in ("svc_a", "svc_b"):
        for f, x, y in zip(seq[name]._fields, seq[name], coalesced[name]):
            np.testing.assert_array_equal(
                np.asarray(x), np.asarray(y),
                err_msg=f"service coalescing drift: {name}.{f}",
            )

    # --- durable-execution bitwise tripwire ------------------------------
    # a segmented run killed at a boundary and resumed from its snapshot
    # must be bitwise the straight run — the ISSUE-9 invariant, in seconds
    import tempfile

    from repro.api.store import ResultStore
    from repro.utils.faults import FaultPlan, Kill, SimulatedKill

    straight = plan.sweep_stacked([scen[0]], seeds=2, base_key=5)
    with tempfile.TemporaryDirectory() as tmp:
        store = ResultStore(tmp)
        fp = FaultPlan().skip("segment.boundary", 1).at(
            "segment.boundary", Kill()
        )
        killed = False
        try:
            with fp.active():
                plan.sweep_stacked([scen[0]], seeds=2, base_key=5,
                                   store=store, segment_steps=20)
        except SimulatedKill:
            killed = True
        assert killed, "the boundary kill must fire"
        resumed = plan.sweep_stacked([scen[0]], seeds=2, base_key=5,
                                     store=store, segment_steps=20)
    for name, a, b in zip(straight._fields, straight, resumed):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b),
            err_msg=f"resume drift vs straight run: {name}",
        )

    print("SMOKE ok: estimator impls agree (round bitwise, trajectories); "
          "zoo neutral row bitwise == plain config; legacy shims bitwise == "
          "Experiment API; coalesced service == sequential sweep bitwise; "
          "kill-and-resume bitwise == straight run")


def main() -> None:
    use_compile_cache()
    if "--smoke" in sys.argv[1:]:
        t0 = time.time()
        smoke()
        print(f"# smoke wall time: {time.time() - t0:.1f}s", file=sys.stderr)
        return
    names = [a for a in sys.argv[1:] if not a.startswith("-")] or list(BENCHES)
    print("name,us_per_call,derived")
    t0 = time.time()
    for name in names:
        if name not in BENCHES:
            raise SystemExit(f"unknown benchmark {name!r}; have {list(BENCHES)}")
        BENCHES[name]()
    print(f"# total wall time: {time.time() - t0:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
