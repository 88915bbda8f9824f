"""Sweep engine on the Plan surface: batched scenarios are bitwise the
per-scenario ensembles, grouping/stacking behave, placement dispatches."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import Experiment, Placement
from repro.api import plan as plan_mod
from repro.core import FailureConfig, ProtocolConfig
from repro.graphs import random_regular_graph
from repro.sweep import (
    Scenario,
    group_scenarios,
    stack_configs,
)

N, W, Z0, STEPS, SEEDS = 24, 10, 5, 60, 2


@pytest.fixture(scope="module")
def graph():
    return random_regular_graph(N, 4, seed=3)


def _pcfg(alg, impl, **kw):
    base = dict(
        algorithm=alg, z0=Z0, max_walks=W, rt_bins=32, protocol_start=10,
        estimator_impl=impl,
    )
    base.update(kw)
    return ProtocolConfig(**base)


def _fcfgs():
    return [
        FailureConfig(burst_times=(20,), burst_sizes=(2,)),
        FailureConfig(burst_times=(25,), burst_sizes=(1,), p_fail=0.002),
        FailureConfig(
            burst_times=(30,), burst_sizes=(2,),
            byzantine_node=1, p_byz=0.01, byz_start_time=15,
        ),
    ]


def _sweep_stacked(graph, scenarios, *, seeds=SEEDS, base_key=0, **kw):
    return Experiment(graph=graph, scenarios=scenarios, steps=STEPS,
                      **kw).plan().sweep_stacked(seeds=seeds, base_key=base_key)


def _ensemble(graph, pcfg, fcfg, *, seeds=SEEDS, base_key=0):
    return Experiment(graph=graph, protocol=pcfg, failures=fcfg,
                      steps=STEPS).ensemble(seeds, base_key=base_key)


def _assert_outputs_equal(ref, got, label):
    for name, a, b in zip(ref._fields, ref, got):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b), err_msg=f"{label}: field {name}"
        )


@pytest.mark.slow
@pytest.mark.parametrize("impl", ["gather", "compare"])
@pytest.mark.parametrize("alg", ["decafork", "decafork+", "missingperson", "none"])
def test_sweep_matches_ensemble(graph, alg, impl):
    """Plan.sweep_stacked over a scenario stack == per-scenario
    Plan.ensemble, bitwise."""
    eps_grid = (1.4, 1.8, 2.2)
    scenarios = [
        (_pcfg(alg, impl, eps=e, eps2=5.0 + e, eps_mp=15.0 + 10 * i), f)
        for i, (e, f) in enumerate(zip(eps_grid, _fcfgs()))
    ]
    out = _sweep_stacked(graph, scenarios, base_key=7)
    assert out.z.shape == (len(scenarios), SEEDS, STEPS)
    for i, (pc, fc) in enumerate(scenarios):
        ref = _ensemble(graph, pc, fc, base_key=7)
        got = jax.tree_util.tree_map(lambda x: x[i], out)
        _assert_outputs_equal(ref, got, f"{alg}/{impl}/scenario{i}")


def test_sweep_single_compilation(graph):
    """>= 8 scenarios x >= 4 seeds execute as ONE jit-compiled call, and
    numeric grid changes reuse the cached executable."""
    fcs = [
        FailureConfig(burst_times=(20,), burst_sizes=(2,)),
        FailureConfig(burst_times=(25,), burst_sizes=(2,), p_fail=0.001),
    ]
    scenarios = [
        (_pcfg("decafork", "gather", eps=e), fc)
        for e in (1.5, 1.8, 2.1, 2.4)
        for fc in fcs
    ]
    assert len(scenarios) >= 8
    sweep_compiles = lambda: plan_mod.cache_stats()["by_mode"].get("sweep", 0)
    before = sweep_compiles()
    out = _sweep_stacked(graph, scenarios, seeds=4, base_key=11)
    jax.block_until_ready(out.z)
    after_first = sweep_compiles()
    assert after_first <= before + 1  # one (possibly pre-cached) program
    assert out.z.shape == (8, 4, STEPS)
    # and that one program reproduces every per-scenario ensemble bitwise
    for i, (pc, fc) in enumerate(scenarios):
        ref = _ensemble(graph, pc, fc, seeds=4, base_key=11)
        got = jax.tree_util.tree_map(lambda x: x[i], out)
        _assert_outputs_equal(ref, got, f"scenario{i}")
    # numeric variations reuse the same program: a second grid, same shapes
    more = [
        (_pcfg("decafork", "gather", eps=e), fcs[0]) for e in np.linspace(1.2, 2.6, 8)
    ]
    _sweep_stacked(graph, more, seeds=4, base_key=13)
    assert sweep_compiles() == after_first


@pytest.mark.slow
def test_burst_padding_batches_unequal_schedules(graph):
    """Scenarios with different burst counts co-batch via pad_bursts."""
    scenarios = [
        (_pcfg("decafork", "gather", eps=1.8),
         FailureConfig(burst_times=(15, 35), burst_sizes=(2, 1))),
        (_pcfg("decafork", "gather", eps=2.0),
         FailureConfig(burst_times=(25,), burst_sizes=(2,))),
    ]
    out = _sweep_stacked(graph, scenarios, base_key=5)
    for i, (pc, fc) in enumerate(scenarios):
        ref = _ensemble(graph, pc, fc, base_key=5)
        np.testing.assert_array_equal(np.asarray(out.z[i]), np.asarray(ref.z))


def test_stack_rejects_mixed_static_structure():
    a = _pcfg("decafork", "gather")
    b = _pcfg("missingperson", "gather")
    fc = FailureConfig()
    with pytest.raises(ValueError, match="static structures"):
        stack_configs([(a, fc), (b, fc)])
    # fork_prob None vs value is a structure change, too
    c = _pcfg("decafork", "gather", fork_prob=0.2)
    with pytest.raises(ValueError, match="static structures"):
        stack_configs([(a, fc), (c, fc)])


def test_sweep_stacked_rejects_mixed_structures(graph):
    """Plan.sweep_stacked is the single-structure entry: mixed lists must
    go through Plan.sweep (which groups them)."""
    fc = FailureConfig()
    scenarios = [
        (_pcfg("decafork", "gather"), fc),
        (_pcfg("missingperson", "gather"), fc),
    ]
    with pytest.raises(ValueError, match="static structures"):
        _sweep_stacked(graph, scenarios)


@pytest.mark.slow
def test_sweep_mixes_groups(graph):
    """Mixed algorithms group into per-structure batches, order preserved
    — Plan.groups exposes the partition, Plan.sweep runs it."""
    fc = FailureConfig(burst_times=(20,), burst_sizes=(2,))
    scenarios = [
        Scenario("dfk/1.6", _pcfg("decafork", "gather", eps=1.6), fc),
        Scenario("mp", _pcfg("missingperson", "gather", eps_mp=25.0), fc),
        Scenario("dfk/2.0", _pcfg("decafork", "gather", eps=2.0), fc),
        Scenario("none", _pcfg("none", "gather"), FailureConfig()),
    ]
    exp = Experiment(graph=graph, scenarios=scenarios, steps=STEPS)
    plan = exp.plan()
    assert [idxs for _, idxs in plan.groups()] == [[0, 2], [1], [3]]
    assert plan.groups() == group_scenarios(scenarios)
    res = plan.sweep(seeds=SEEDS, base_key=3)
    assert res.names == ("dfk/1.6", "mp", "dfk/2.0", "none")
    for s, out in zip(scenarios, res.outputs):
        ref = _ensemble(graph, s.pcfg, s.fcfg, base_key=3)
        _assert_outputs_equal(ref, out, s.name)
    assert res["mp"] is res.outputs[1]


def test_placement_policies_agree_on_single_device(graph):
    """Explicit sharded placement is a correctness no-op on 1 device."""
    fc = FailureConfig(burst_times=(20,), burst_sizes=(2,))
    scenarios = [(_pcfg("decafork", "gather", eps=e), fc) for e in (1.6, 2.0)]
    a = _sweep_stacked(graph, scenarios, base_key=9, placement="sharded")
    b = _sweep_stacked(graph, scenarios, base_key=9, placement="local")
    np.testing.assert_array_equal(np.asarray(a.z), np.asarray(b.z))


def test_placement_dispatch(graph, monkeypatch):
    """The Plan consults exactly its Placement policy: 'local' never
    touches device placement, 'auto'/'sharded' go through place()."""
    import repro.api.placement as plc

    calls = []
    real = plc.Placement.place

    def spy(self, pcfgs, fcfgs, n_scenarios):
        calls.append(self.policy)
        return real(self, pcfgs, fcfgs, n_scenarios)

    monkeypatch.setattr(plc.Placement, "place", spy)
    fc = FailureConfig(burst_times=(20,), burst_sizes=(2,))
    scenarios = [(_pcfg("decafork", "gather", eps=e), fc) for e in (1.6, 2.0)]

    def run(placement):
        return Experiment(
            graph=graph, scenarios=scenarios, steps=5, placement=placement,
        ).plan().sweep_stacked(seeds=1)

    run("local")
    run(None)  # resolves to auto
    run(Placement.SHARDED)
    assert calls == ["local", "auto", "sharded"]
    with pytest.raises(ValueError, match="placement policy"):
        Placement("everywhere")
    with pytest.raises(TypeError, match="placement"):
        Experiment(graph=graph, scenarios=scenarios, steps=5, placement=7)


def test_scenario_blocks_under_shard_map_are_bitwise(graph):
    """A sweep whose scenario axis is split over a 'data' mesh — each
    device's block under shard_map, as a placed sweep runs on several
    chips — is bitwise the unsplit sweep (one block on this device)."""
    from repro.core import simulator as sim
    from repro.launch.mesh import make_local_mesh

    scenarios = [
        (_pcfg("decafork", "gather", eps=e), f)
        for e, f in zip((1.6, 2.0, 2.4), _fcfgs())
    ]
    pcfgs, fcfgs = stack_configs(scenarios)
    plan = Experiment(graph=graph, scenarios=scenarios, steps=STEPS).plan()
    keys = jax.random.split(jax.random.key(5), SEEDS)
    core = jax.jit(
        sim._sweep_core, static_argnames=("steps", "n", "payload", "spec",
                                          "pspec", "mesh"),
    )
    args = (keys, plan.neighbors, plan.degrees, plan.mirror, None, pcfgs,
            fcfgs)
    got = core(*args, steps=STEPS, n=graph.n, mesh=make_local_mesh())
    want = core(*args, steps=STEPS, n=graph.n)
    _assert_outputs_equal(want, got, "shard_map blocks")


def test_placement_from_legacy_tristate():
    """Placement.from_sharded maps the legacy tri-state by identity:
    bool-equal ints must not silently alias into the wrong policy."""
    assert Placement.from_sharded(None) is Placement.AUTO
    assert Placement.from_sharded(True) is Placement.SHARDED
    assert Placement.from_sharded(False) is Placement.LOCAL
    for bad in (0, 1, "auto"):
        with pytest.raises(TypeError, match="sharded"):
            Placement.from_sharded(bad)


def test_traced_config_leaves_do_not_recompile(graph):
    """Numeric knobs are traced: one Plan executable serves a whole
    epsilon x failure-rate grid of ensembles (the pre-sweep per-curve
    compile storm is gone)."""
    first = None
    for e in (1.5, 1.9, 2.3):
        for pf in (0.0, 0.002):
            _ensemble(
                graph,
                _pcfg("decafork", "gather", eps=e),
                FailureConfig(burst_times=(20,), burst_sizes=(2,), p_fail=pf),
            )
            if first is None:
                first = plan_mod.cache_stats()["xla_compiles"]
    # every (eps, p_fail) combination after the first reused its program
    assert plan_mod.cache_stats()["xla_compiles"] == first
