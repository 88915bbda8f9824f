"""Device time by program stage and idle gaps named by program spans."""
import gzip
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from chipbench import stages, trace

RECORDED = Path(__file__).with_name("data") / "tpu_stage_excerpt.json.gz"
SCOPES = {
    "fusion.1": "jit(_run_ensemble_core)/vmap()/while/body/closed_call/round.topology/add",
    "custom-call": "jit(_run_ensemble_core)/vmap()/while/body/round.kernel/pallas_call",
    "fusion.2": "jit(_run_ensemble_core)/vmap()/while/body/payload.fork/scatter",
    "copy": "jit(_run_ensemble_core)/vmap()/while/body/dynamic_update_slice",
}


def _planes(ops, spans, scopes=SCOPES):
    return [
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": spans}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [["jit_run", 0, 10_000]]},
            {"name": "XLA Ops", "events": ops, "scopes": scopes},
        ]},
    ]


@pytest.mark.parametrize("path, stage", [
    ("jit(f)/vmap()/while/body/round.fork/scatter", "round.fork"),
    ("jit(f)/while/body/payload.step/jvp(dot_general)", "payload.step"),
    ("jit(f)/round.decide/jit(inner)/round.fork/gather", "round.fork"),  # innermost
    ("jit(f)/while/body/dynamic_update_slice", stages.NONE),
    ("", stages.NONE),
    (None, stages.NONE),
])
def test_stage_is_the_innermost_round_or_payload_scope(path, stage):
    assert stages.stage_of(path) == stage


def test_stage_seconds_inside_the_window_with_unscoped_ops_under_none():
    ops = [["fusion.1", 500, 1_500],  # clipped at the window's start: 1_000
           ["custom-call", 2_500, 1_000],
           ["fusion.2", 6_000, 1_000],
           ["copy", 9_500, 3_000],  # clipped at 10_000: 500, no stage
           ["fusion.9", 7_000, 200],  # in no scopes table: no stage
           ["while.3", 1_000, 9_000]]  # the loop: busy, but no stage
    spans = [["bench.window", 1_000, 9_000]]
    s = stages.reduce(_planes(ops, spans), devices=1)
    assert s.stages == {
        "round.kernel": pytest.approx(1e-6),
        "round.topology": pytest.approx(1e-6),
        "payload.fork": pytest.approx(1e-6),
        stages.NONE: pytest.approx(0.7e-6),
    }
    assert s.unscoped == [["copy", pytest.approx(0.5e-6), SCOPES["copy"]],
                          ["fusion.9", pytest.approx(0.2e-6), None]]
    # busy is trace.reduce's: the loop covers the whole window
    assert s.busy_s == pytest.approx(trace.reduce(_planes(ops, spans), 1).busy_s)
    assert s.window_s == pytest.approx(9e-6)


def test_gap_labels_append_the_innermost_open_program_span():
    ops = [["fusion.1", 1_000, 1_000], ["fusion.2", 4_000, 1_000],
           ["copy", 8_000, 1_000]]
    spans = [["bench.window", 1_000, 9_000],
             ["bench.dispatch", 2_000, 2_000],  # gap [2000, 4000]
             ["plan.ensemble", 2_100, 1_800],
             ["plan.enqueue", 2_800, 1_100],  # open at the gap's midpoint 3000
             ["bench.fetch", 5_000, 3_000]]  # gap [5000, 8000]: no program span
    s = stages.reduce(_planes(ops, spans), devices=1)
    assert s.gaps == [["bench.fetch", pytest.approx(3e-6)],
                      ["bench.dispatch/plan.enqueue", pytest.approx(2e-6)],
                      ["bench.window", pytest.approx(1e-6)]]  # [9000, 10000]
    # without program spans the labels are trace.reduce's
    plain = [sp for sp in spans if not sp[0].startswith("plan.")]
    assert stages.reduce(_planes(ops, plain), 1).gaps == trace.reduce(
        _planes(ops, plain), 1).gaps


def test_a_trace_without_scopes_puts_every_op_under_none():
    ops = [["fusion.1", 1_000, 2_000], ["copy", 4_000, 1_000]]
    s = stages.reduce(_planes(ops, [["bench.window", 0, 10_000]], scopes={}), 1)
    assert s.stages == {stages.NONE: pytest.approx(3e-6)}


def test_a_trace_without_the_window_span_is_refused():
    with pytest.raises(ValueError, match="bench.window"):
        stages.reduce(_planes([], []), devices=1)


def _pb(*fields):
    """Protobuf wire format of ``(field number, int | str | bytes)``."""
    def varint(n):
        out = bytearray()
        while True:
            out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
            n >>= 7
            if not n:
                return bytes(out)
    out = b""
    for num, value in fields:
        if isinstance(value, int):
            out += varint(num << 3) + varint(value)
        else:
            value = value.encode() if isinstance(value, str) else value
            out += varint(num << 3 | 2) + varint(len(value)) + value
    return out


def test_op_scopes_reads_the_scope_stat_of_each_op_s_metadata(tmp_path):
    path_a = "jit(f)/while/body/round.fork/scatter:"
    path_b = "jit(f)/while/body/round.kernel/pallas_call:"
    # stat metadata 7: the scope stat, 8: a string it refers to, 9: other
    device = _pb(
        (2, "/device:TPU:0"),
        (3, _pb((2, "XLA Ops"), (4, _pb((1, 1), (2, 5))))),  # a line: skipped
        (4, _pb((1, 1), (2, _pb((1, 1), (2, "%fusion.1 = f32[8] fusion(%p)"),
                                (5, _pb((1, 7), (5, path_a))))))),
        (4, _pb((1, 2), (2, _pb((1, 2), (2, "%custom-call.3 = f32[8] custom-call()"),
                                (5, _pb((1, 9), (5, "ignored"))),
                                (5, _pb((1, 7), (7, 8))))))),  # by reference
        (4, _pb((1, 3), (2, _pb((1, 3), (2, "%copy.4 = f32[8] copy()"))))),
        (5, _pb((1, 7), (2, _pb((1, 7), (2, stages.SCOPE_STAT))))),
        (5, _pb((1, 8), (2, _pb((1, 8), (2, path_b))))),
        (5, _pb((1, 9), (2, _pb((1, 9), (2, "flops"))))),
    )
    host = _pb((2, "/host:CPU"), (4, _pb((1, 1), (2, _pb((1, 1), (2, "x"))))))
    f = tmp_path / "t.xplane.pb"
    f.write_bytes(_pb((1, host), (1, device)))
    assert stages.op_scopes(str(f)) == {"/device:TPU:0": {
        "%fusion.1 = f32[8] fusion(%p)": path_a,
        "%custom-call.3 = f32[8] custom-call()": path_b,
    }}


def test_recorded_tpu_stage_excerpt():
    with gzip.open(RECORDED, "rt") as f:
        planes = json.load(f)["planes"]
    s = stages.reduce(planes, devices=1)
    t = trace.reduce(planes, devices=1)
    assert (s.window_s, s.busy_s) == (t.window_s, t.busy_s)
    assert 0 < s.busy_s <= s.window_s
    # every op but the loop has a stage or falls under "(none)", and the
    # ops do not overlap, so the stages add up to the busy time
    assert sum(s.stages.values()) == pytest.approx(s.busy_s, rel=0.01)
    assert stages.NONE in s.stages
    assert {"round.kernel", "round.fork", "round.topology"} <= set(s.stages)
    # before the scan starts, the host is inside Plan.ensemble's enqueue
    assert s.gaps[0][0] == "bench.dispatch/plan.enqueue"


def test_measure_runs_a_tiny_cell_untraced_then_traced_on_the_cpu():
    """The CLI's path without a chip: both windows finish studies, and
    the traced one carries the benchmark's and the program's spans,
    nested as the gap labels assume."""
    import dataclasses

    import tinycells

    cell = tinycells.tiny("paper-regular100.fig1-decafork")
    # few rounds: a CPU trace records every op of every round on the host
    cell = dataclasses.replace(cell, config=dict(cell.config, steps=200))
    windows, planes = stages.measure(cell, 2**31 + 13, 0.1, require_tpu=False)
    assert windows["untraced"][0] >= 1 and windows["traced"][0] >= 1
    spans = stages._host_spans(planes, "")
    names = [s[2] for s in spans]
    for name in ("bench.window", "bench.dispatch", "bench.fetch",
                 "plan.ensemble", "plan.prepare", "plan.enqueue"):
        assert name in names
    dispatch = [s for s in spans if s[2] == "bench.dispatch"]
    for s in spans:
        if s[2].startswith("plan."):
            assert any(d[0] <= s[0] <= s[1] <= d[1] for d in dispatch)


def test_the_cli_refuses_a_machine_without_a_tpu():

    root = Path(__file__).resolve().parents[2]
    r = subprocess.run(
        [sys.executable, "chipbench/stages.py", "--workload",
         "paper-regular100.fig1-decafork", "--seed", "1", "--seconds", "1"],
        cwd=root, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=300,
    )
    assert r.returncode != 0 and r.stdout.strip() == ""
    assert "no TPU" in r.stderr
