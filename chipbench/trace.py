"""Reduce a profiler trace to device busy time, top ops and idle gaps.

Input is what ``jax.profiler`` writes (``*.xplane.pb``), read with
``jax.profiler.ProfileData``, or the same structure as plain data
(``{"planes": [{"name", "lines": [{"name", "events": [[name, start_ns,
duration_ns], ...]}]}]}``) for a recorded excerpt. Device planes are
``/device:TPU:<i>``; their op line is ``XLA Ops``. Host spans are the
benchmark's own ``bench.*`` annotations on the host planes, and the
window is the ``bench.window`` span.
"""
from __future__ import annotations

import dataclasses
import glob
import os

OP_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
# ops that hold other ops: busy while their body runs, ranked by the body
CONTAINERS = ("while", "conditional", "call")


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float  # mean over the devices read
    devices: int
    ops: list  # [[op, seconds]] by total time, mean over devices; loops left out
    gaps: list  # [[span open during the gap, seconds]] longest first


def planes_from_file(path: str) -> list:
    """The trace as plain data (see module docstring)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    return [
        {
            "name": plane.name,
            "lines": [
                {
                    "name": line.name,
                    "events": [[e.name, e.start_ns, e.duration_ns] for e in line.events],
                }
                for line in plane.lines
            ],
        }
        for plane in data.planes
    ]


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def op_name(event_name: str) -> str:
    """``fusion.12`` of ``%fusion.12 = f32[8]{0} fusion(...)``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _union(intervals):
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def reduce(planes: list, devices: int, top: int = 10) -> Summary:
    """Busy and idle time of the first ``devices`` device planes inside
    the ``bench.window`` span."""
    spans = [
        (ev[1], ev[1] + ev[2], ev[0])
        for p in planes
        if p["name"].startswith("/host:")
        for line in p["lines"]
        for ev in line["events"]
        if ev[0].startswith(SPAN_PREFIX)
    ]
    windows = [s for s in spans if s[2] == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"trace has no {WINDOW_SPAN} span")
    w0, w1 = windows[0][0], windows[0][1]
    dev_planes = sorted(
        (p for p in planes if p["name"].startswith("/device:TPU:")),
        key=lambda p: int(p["name"].rsplit(":", 1)[1]),
    )[:devices]
    if not dev_planes:
        raise ValueError("trace has no TPU device plane")
    busy_total = 0.0
    op_time: dict = {}
    gaps = []
    for p in dev_planes:
        ivs = []
        for line in p["lines"]:
            if line["name"] != OP_LINE:
                continue
            for name, start, dur in line["events"]:
                a, b = max(start, w0), min(start + dur, w1)
                if b > a:
                    ivs.append((a, b))
                    short = op_name(name)
                    if not short.startswith(CONTAINERS):
                        op_time[short] = op_time.get(short, 0.0) + (b - a)
        merged = _union(ivs)
        busy_total += sum(b - a for a, b in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps += [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    n = len(dev_planes)
    inner = [s for s in spans if s[2] != WINDOW_SPAN]

    def host_span(a, b):
        mid = (a + b) / 2
        open_ = [s for s in inner if s[0] <= mid < s[1]]
        return max(open_)[2] if open_ else WINDOW_SPAN

    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    return Summary(
        window_s=(w1 - w0) / 1e9,
        busy_s=busy_total / n / 1e9,
        devices=n,
        ops=[
            [name, t / n / 1e9]
            for name, t in sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
        ],
        gaps=[[host_span(a, b), (b - a) / 1e9] for a, b in gaps[:top]],
    )
