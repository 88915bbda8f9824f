"""Ahead-of-time compiles of the Pallas kernels for a TPU v5e chip.

The CPU suite runs every kernel in interpret mode, which accepts
constructs the chip's compiler (Mosaic) refuses. These tests compile the
three kernels for a described — not attached — v5e chip at the paper
cell's shapes (n=100 nodes, not a multiple of the 8-row tile; 64 walk
slots and tracking columns; 1024 return-time bins; degree 8; 2 bursts),
alone and under ``jax.vmap`` over a 50-seed axis as ``Plan.ensemble``
calls them. Nothing runs: a pass says the chip's compiler accepts the
kernel, not that it is right (the interpret-mode oracle tests say that)
or fast.

The topology is described inside a fixture, never at import: only one
process may load the TPU compiler library at a time, and every xdist
worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

N, W, B, D, K, SEEDS = 100, 64, 1024, 8, 2, 50
C = W


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        # the TPU compiler logs under /tmp unless told otherwise
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            desc = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent
        # cache but cannot be read back without one: keep it out
        was_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", was_on)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(sharding, batch, *specs):
    lead = () if batch is None else (batch,)
    return tuple(
        jax.ShapeDtypeStruct(lead + tuple(shape), dtype, sharding=sharding)
        for shape, dtype in specs
    )


def _compile_kernel(fn, args, batch):
    f = jax.vmap(fn) if batch else fn
    text = jax.jit(f).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text  # the Mosaic kernel, not a fallback


i32, i16, f32, bool_ = jnp.int32, jnp.int16, jnp.float32, jnp.bool_
BATCHES = [pytest.param(None, id="single"), pytest.param(SEEDS, id="vmap50")]


@pytest.mark.parametrize("batch", BATCHES)
def test_round_update_compiles_for_v5e(one_chip, batch):
    from repro.kernels.round_update import round_update_pallas

    args = _shapes(
        one_chip, batch,
        ((N, C), i32), ((N, B), i16), ((N,), i32),  # last_seen, hist, total
        ((W,), i32), ((W,), i32), ((W,), i32),  # pos, track, r
        ((W,), bool_), ((W,), i32), ((), i32),  # valid, upd, t
    )
    _compile_kernel(
        lambda *a: round_update_pallas(*a, interpret=False), args, batch
    )


@pytest.mark.parametrize("batch", BATCHES)
def test_theta_sums_compiles_for_v5e(one_chip, batch):
    from repro.kernels.theta_survival import theta_sums

    args = _shapes(
        one_chip, batch, ((N, C), i32), ((N, B), i16), ((N,), i32), ((), i32)
    )
    _compile_kernel(lambda *a: theta_sums(*a, interpret=False), args, batch)


@pytest.mark.parametrize("batch", BATCHES)
@pytest.mark.parametrize(
    "decafork_plus", [False, True], ids=["decafork", "decafork+"]
)
def test_whole_round_compiles_for_v5e(one_chip, decafork_plus, batch):
    from repro.kernels.round_update import whole_round_pallas

    args = _shapes(
        one_chip, batch,
        ((N, C), i32), ((N, B), i16), ((N,), i32),  # observation state
        ((N,), bool_), ((N, D), bool_),  # node_up, edge_up
        ((W,), i32), ((W,), i32), ((W,), bool_),  # pos, track, active
        ((W, D), i32), ((W,), i32), ((W, D), bool_),  # walk-row adjacency
        ((W, D), f32), ((W, D), f32),  # walk-row link uniforms
        ((W,), f32), ((W,), f32), ((W,), f32), ((W,), f32),  # walk uniforms
        ((K, W), f32), ((K,), i32),  # burst uniforms, sizes
        ((N,), f32), ((N,), f32), ((N,), bool_),  # node uniforms, schedule
        ((N, D), f32), ((N, D), f32),  # link uniforms
        ((1, 8), f32), ((1, 4), i32),  # params
    )
    _compile_kernel(
        lambda *a: whole_round_pallas(
            *a, decafork_plus=decafork_plus, interpret=False
        ),
        args,
        batch,
    )


@pytest.mark.parametrize("alg", ["decafork", "decafork+"])
def test_paper_config_takes_whole_round_kernel_on_tpu(monkeypatch, alg):
    """On a TPU backend the default paper config resolves to the fused
    whole-round Pallas kernel, not the stage-sequence fallback."""
    from benchmarks.common import pcfg_for
    from repro.core import simulator as sim

    monkeypatch.delenv("REPRO_ROUND_IMPL", raising=False)
    monkeypatch.delenv("REPRO_ESTIMATOR_IMPL", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    pcfg = pcfg_for(alg)
    assert sim.resolved_estimator_impl(pcfg) == "fused"
    decision = sim.round_impl_decision(pcfg)
    assert (decision.impl, decision.backend) == ("fused", "pallas"), decision


def _ensemble_hlo(one_chip, protocol, seeds):
    """Optimised HLO of ``Plan.ensemble``'s program over ``seeds`` seeds,
    compiled for the described chip at the paper cell's widths."""
    from repro.api import Experiment
    from repro.api import plan as plan_mod
    from repro.core import FailureConfig
    from repro.graphs import random_regular_graph

    p = Experiment(
        graph=random_regular_graph(N, D, seed=0),
        protocol=protocol,
        failures=FailureConfig(burst_times=(20, 30), burst_sizes=(5, 6)),
        steps=40,
    ).plan()
    pcfg, fcfg = p._require_base("ensemble")
    sig = p._signature("ensemble", pcfg, plan_mod._schedule_lens(fcfg), fcfg)
    args = (jax.random.split(jax.random.key(0), seeds), p.neighbors, p.degrees,
            p.mirror, p._pi(pcfg), pcfg, fcfg)
    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(
            jnp.shape(x), jnp.result_type(x), sharding=one_chip,
            weak_type=not hasattr(x, "dtype"),
        ),
        args,
    )
    return plan_mod.executable("ensemble", sig).lower(
        *shapes, steps=p.steps, n=p.n, payload=None, spec=p.spec, pspec=None,
    ).compile().as_text()


def test_whole_round_program_carries_stage_scopes_for_v5e(one_chip, monkeypatch):
    """``Plan.ensemble``'s TPU program, compiled for a described v5e at
    the paper cell's widths, names its round stages: the pre-drawn
    topology uniforms, the whole-round kernel and the fork machinery each
    under their scope (what a profiler trace of the chip reports)."""
    import re

    from repro.core import ProtocolConfig

    monkeypatch.delenv("REPRO_ROUND_IMPL", raising=False)
    monkeypatch.delenv("REPRO_ESTIMATOR_IMPL", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = _ensemble_hlo(
        one_chip, ProtocolConfig(z0=10, max_walks=W, rt_bins=B, protocol_start=10), 2
    )
    assert "tpu_custom_call" in text
    scopes = {
        part
        for name in re.findall(r'op_name="([^"]*)"', text)
        for part in name.split("/")
        if part.startswith("round.")
    }
    assert {"round.topology", "round.kernel", "round.fork"} <= scopes
    kernel = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert all("round.kernel/" in ln for ln in kernel)


def test_missingperson_round_scatters_no_event_grid_for_v5e(one_chip, monkeypatch):
    """The unfused MissingPerson round, compiled for a described v5e at the
    paper cell's shapes over 50 seeds, allocates fork slots without a
    sort and without a scatter whose updates span the W*C event grid
    (``execute_grid_forks`` works slot-side)."""
    import math
    import re

    from repro.core import ProtocolConfig

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = _ensemble_hlo(
        one_chip,
        ProtocolConfig(algorithm="missingperson", z0=10, max_walks=W, rt_bins=B,
                       protocol_start=10, eps_mp=400.0),
        SEEDS,
    )
    assert "round.fork/" in text and "/while/body/" in text
    assert not re.search(r" sort\(", text)
    size = {
        name: dims
        for name, dims in re.findall(r"^\s*(?:ROOT )?(%\S+) = \w+\[([\d,]*)\]", text, re.M)
    }
    updates = re.findall(r" scatter\(%[^,]+, %[^,]+, (%[^),]+)\)", text)
    assert updates, "no scatter at all: the pattern no longer reads the HLO"
    for name in updates:
        dims = [int(d) for d in size[name].split(",") if d]
        assert math.prod(dims) < SEEDS * W * C, (name, dims)
