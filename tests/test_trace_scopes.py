"""Stage scopes and plan spans: the names a profiler trace carries.

The round's stages run under ``jax.named_scope`` (``round.*``), the
payload hooks under ``payload.*`` (``core/simulator.py``,
``optim/rw_sgd.py``); ``Plan``'s public calls open ``plan.*`` host spans
(``api/plan.py``). These tests read the scopes from the ``op_name``
metadata of the compiled CPU program and the spans from a CPU profiler
trace. That the scopes change no output bit is what the golden and
bitwise tests elsewhere hold.
"""
import glob
import re

import jax
import pytest

from repro.api import Experiment
from repro.api import plan as plan_mod
from repro.core import FailureConfig, ProtocolConfig
from repro.data import make_markov_task
from repro.graphs import random_regular_graph
from repro.models.config import ModelConfig
from repro.models.model import Model
from repro.optim import RwSgdPayload, adamw

N, DEG, W, STEPS, SEEDS = 16, 4, 8, 20, 2
STAGES = ("round.topology", "round.move", "round.threats", "round.observe",
          "round.decide", "round.fork")
_OP = re.compile(r'^\s*(?:ROOT )?%\S+ = \S+ ([\w-]+)\(.*?op_name="([^"]*)"')


def _experiment(alg="decafork", payload=None, **kw):
    pcfg = ProtocolConfig(
        algorithm=alg, z0=3, max_walks=W, rt_bins=32, protocol_start=5, **kw
    )
    return Experiment(
        graph=random_regular_graph(N, DEG, seed=0), protocol=pcfg,
        failures=FailureConfig(burst_times=(8,), burst_sizes=(1,)),
        steps=STEPS, payload=payload,
    )


def _body_ops(exp):
    """``[(opcode, op_name)]`` of the scan body of ``Plan.ensemble``'s
    compiled CPU program."""
    p = exp.plan()
    pcfg, fcfg = p._require_base("ensemble")
    keys = jax.random.split(jax.random.key(0), SEEDS)
    sig = p._signature("ensemble", pcfg, plan_mod._schedule_lens(fcfg), fcfg)
    text = plan_mod.executable("ensemble", sig).lower(
        keys, p.neighbors, p.degrees, p.mirror, p._pi(pcfg), pcfg, fcfg,
        steps=p.steps, n=p.n, payload=p.payload, spec=p.spec, pspec=p.pspec,
    ).compile().as_text()
    ops = [m.groups() for m in map(_OP.match, text.splitlines()) if m]
    return [(op, name) for op, name in ops
            if "/while/body/" in name and op != "parameter"]


def _stage(name):
    parts = [p for p in name.split("/") if p.startswith(("round.", "payload."))]
    return parts[-1] if parts else None


def _stages_of(ops, marker):
    """The stages of every op whose scope path has ``marker`` as a
    component (a primitive, or the name of a jitted function)."""
    found = {_stage(name) for _, name in ops if marker in name.split("/")}
    assert found, f"no op carries {marker!r}"
    return found


@pytest.fixture(scope="module")
def decafork_ops():
    exp = _experiment()
    decision = exp.plan().round_decisions()[0][2]
    assert (decision.impl, decision.backend) == ("fused", "ref"), decision
    return _body_ops(exp)


def test_fused_reference_round_carries_every_stage(decafork_ops):
    assert set(STAGES) <= {_stage(name) for _, name in decafork_ops}
    assert _stages_of(decafork_ops, "topology_uniforms") == {"round.topology"}
    assert _stages_of(decafork_ops, "select_available_edge") == {"round.move"}
    # the last-seen max-update and the cumulative return-time table
    assert _stages_of(decafork_ops, "scatter-max") == {"round.observe"}
    # slot writes of execute_terminations / execute_forks
    assert _stages_of(decafork_ops, "scatter") == {"round.fork"}


def test_unfused_missingperson_round_scopes_the_event_grid():
    exp = _experiment("missingperson", eps_mp=30.0)
    decision = exp.plan().round_decisions()[0][2]
    assert decision.impl == "unfused", decision
    ops = _body_ops(exp)
    assert set(STAGES) <= {_stage(name) for _, name in ops}
    # execute_grid_forks ranks the free slots and the grid's rows, and
    # reads each slot's parent row, by small exact products; it scatters,
    # sorts and cumsums nothing
    assert _stages_of(ops, "dot_general") == {"round.fork"}
    fork = {op for op, name in ops if _stage(name) == "round.fork"}
    assert "dot" in fork, "no rank of the event grid under round.fork"
    assert not fork & {"scatter", "sort", "reduce-window"}, fork
    assert _stages_of(ops, "select_available_edge") == {"round.move"}


def test_payload_hooks_carry_their_scopes():
    cfg = ModelConfig(
        name="tiny", arch_type="dense", num_layers=1, d_model=32, d_ff=64,
        vocab_size=64, num_heads=2, num_kv_heads=2, head_dim=16,
        dtype="float32",
    )
    payload = RwSgdPayload(
        Model(cfg), adamw(1e-2), make_markov_task(cfg.vocab_size, rank=4),
        max_walks=W, local_batch=1, seq_len=8,
    )
    ops = _body_ops(_experiment(payload=payload))
    found = {_stage(name) for _, name in ops}
    assert {"payload.fork", "payload.batch", "payload.step"} | set(STAGES) <= found
    # fork_replica: every leaf's leaf[src] gather and slot scatter
    fork_ops = {op for op, name in ops if _stage(name) == "payload.fork"}
    assert {"gather", "scatter"} <= fork_ops
    # the model's matmuls are the local step's, and only its
    assert _stages_of(ops, "dot_general") == {"payload.step"}


def test_plan_spans_nest_on_the_host_plane(tmp_path):
    from jax.profiler import ProfileData

    p = _experiment().plan()
    jax.block_until_ready(p.ensemble(SEEDS, base_key=1))  # compile untraced
    jax.profiler.start_trace(str(tmp_path))
    jax.block_until_ready(p.ensemble(SEEDS, base_key=2))
    jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = {
        e.name: (e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for e in line.events
        if e.name.startswith("plan.")
    }
    assert set(spans) == {"plan.ensemble", "plan.prepare", "plan.enqueue"}
    a, b, attrs = spans["plan.ensemble"]
    for child in ("plan.prepare", "plan.enqueue"):
        assert a <= spans[child][0] <= spans[child][1] <= b
    assert spans["plan.prepare"][1] <= spans["plan.enqueue"][0]
    assert int(attrs["seeds"]) == SEEDS
    assert (int(attrs["new_slots"]), int(attrs["compiled"])) == (0, 0)
    assert re.fullmatch(r"[0-9a-f]{8}", str(attrs["signature"]))
