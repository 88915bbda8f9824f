"""The trace reduction: busy time, named-op time and idle gaps."""
import gzip
import json
from pathlib import Path

import numpy as np
import pytest

from chipbench import trace

RECORDED = Path(__file__).with_name("data") / "tpu_trace_excerpt.json.gz"


def _planes(ops, spans):
    return [
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": spans}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [["jit_run", 0, 10_000]]},
            {"name": "XLA Ops", "events": ops},
        ]},
    ]


def test_busy_is_the_union_of_op_intervals_inside_the_window():
    ops = [["fusion.1", 1_000, 2_000], ["custom-call", 2_500, 1_000],  # overlap
           ["fusion.1", 6_000, 1_000], ["copy", 9_500, 3_000]]  # clipped at 10_000
    spans = [["bench.window", 1_000, 9_000], ["bench.dispatch", 1_000, 500],
             ["bench.fetch", 3_600, 2_000]]
    s = trace.reduce(_planes(ops, spans), devices=1)
    assert s.window_s == pytest.approx(9e-6)
    busy = 2_500 + 1_000 + 500  # [1000,3500] + [6000,7000] + [9500,10000]
    assert s.busy_s == pytest.approx(busy / 1e9)
    assert s.ops[0] == ["fusion.1", pytest.approx(3e-6)]
    assert [name for name, _ in s.ops] == ["fusion.1", "custom-call", "copy"]
    # gaps [3500,6000] (during fetch), [7000,9500] (no inner span)
    assert s.gaps == [["bench.fetch", pytest.approx(2.5e-6)],
                      ["bench.window", pytest.approx(2.5e-6)]]


def test_a_trace_without_the_window_span_is_refused():
    with pytest.raises(ValueError, match="bench.window"):
        trace.reduce(_planes([], []), devices=1)


def test_recorded_tpu_trace_excerpt():
    with gzip.open(RECORDED, "rt") as f:
        planes = json.load(f)["planes"]
    s = trace.reduce(planes, devices=1)
    (w0, dur), = [e[1:] for p in planes if p["name"] == "/host:CPU"
                  for e in p["lines"][0]["events"] if e[0] == "bench.window"]
    # busy by brute force: mark every nanosecond some op covers
    covered = np.zeros(int(dur), bool)
    ops = next(ln for p in planes if p["name"] == "/device:TPU:0"
               for ln in p["lines"] if ln["name"] == "XLA Ops")["events"]
    for _, start, d in ops:
        covered[max(int(start - w0), 0):max(int(start + d - w0), 0)] = True
    assert s.window_s == dur / 1e9
    assert s.busy_s == pytest.approx(covered.sum() / 1e9, rel=1e-12)
    assert 0 < s.busy_s < s.window_s
    # the loop op spans the whole study and is left out of the ranking
    assert s.ops[0][0].startswith("whole_round_pallas")
    assert not any(name.startswith("while") for name, _ in s.ops)
    # the longest gap: the host dispatching the next study
    assert s.gaps[0][0] == "bench.dispatch"
