"""Device time by program stage, and idle gaps named by program spans.

The program names its device work with ``jax.named_scope``: the round's
stages ``round.topology``, ``round.move``, ``round.threats``,
``round.observe``, ``round.decide``, ``round.kernel`` and ``round.fork``
(``core/simulator.py``), the payload's ``payload.fork``, ``payload.batch``
and ``payload.step`` (``core/simulator.py``, ``optim/rw_sgd.py``). Its
host calls open ``plan.*`` spans (``api/plan.py``). This module reads
both from the same trace ``trace.py`` reduces, and leaves that reduction
as it is:

- ``planes_from_file`` gives the planes ``trace.planes_from_file`` gives,
  and on each device op line a ``scopes`` table: op event name -> the
  scope path the profiler attaches to the op's metadata (stat
  ``SCOPE_STAT``, which ``jax.profiler.ProfileData`` does not hand out,
  so ``op_scopes`` reads it from the file), once per distinct op.
- ``reduce`` gives device seconds by stage inside ``bench.window`` (mean
  over devices, the scan's loop left out as in ``trace.reduce``, ops
  with no stage under ``NONE``) and the longest idle gaps, each named by
  the ``bench.*`` span open at its midpoint and, when one is open, the
  innermost ``plan.*`` span (``bench.dispatch/plan.enqueue``).

    python3 chipbench/stages.py --workload <cell> --seed <n> --seconds <s>

runs the cell's studies for one window with the profiler off and one
with it on, from the same seed, and prints both windows' studies and
seconds (their seconds per study give what tracing costs) and the traced
window's stages, gaps and longest unscoped ops, as JSON on the last line
of stdout.
"""
from __future__ import annotations

import dataclasses
import sys
from pathlib import Path

if __package__ in (None, ""):  # run as a script: make ``chipbench`` importable
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from chipbench import trace  # noqa: E402

STAGE_PREFIXES = ("round.", "payload.")
PROGRAM_SPAN_PREFIX = "plan."
NONE = "(none)"
SCOPE_STAT = "tf_op"


@dataclasses.dataclass
class StageSummary:
    window_s: float
    busy_s: float  # union of op intervals, mean over devices (as trace.reduce)
    devices: int
    stages: dict  # {stage: seconds}, mean over devices; loops left out
    gaps: list  # [[open bench span[/innermost plan span], seconds]] longest first
    unscoped: list  # [[op text, seconds, its scope path]] of NONE, longest first


def stage_of(path: str | None) -> str:
    """The innermost ``round.*`` / ``payload.*`` component of a scope
    path (``jit(f)/while/body/round.fork/scatter`` -> ``round.fork``)."""
    for part in reversed((path or "").split("/")):
        if part.startswith(STAGE_PREFIXES):
            return part
    return NONE


def _varint(buf, i):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one protobuf message: an int for a
    varint, bytes (a memoryview) for anything else."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {kind} in an xplane")
        yield key >> 3, value


def _map_values(entry):
    return next(v for f, v in _fields(entry) if f == 2)


def op_scopes(path: str) -> dict:
    """``{device plane: {op event name: scope path}}``: the ``SCOPE_STAT``
    stat of each op's event metadata in an ``.xplane.pb``, which
    ``jax.profiler.ProfileData`` does not hand out. Reads the file's
    protobuf wire format directly (XSpace field 1: planes; XPlane 2:
    name, 4: event metadata, 5: stat metadata; XEventMetadata 2: name,
    5: stats; XStat 1: metadata id, 5: string, 7: reference to a stat
    metadata's name), skipping the event lines."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for field, plane in _fields(space):
        if field != 1:
            continue
        name, events, stat_names = "", [], {}
        for f, v in _fields(plane):
            if f == 2:
                name = bytes(v).decode()
            elif f == 4:
                events.append(_map_values(v))
            elif f == 5:
                meta = dict(_fields(_map_values(v)))
                stat_names[meta.get(1, 0)] = bytes(meta.get(2, b"")).decode()
        if not name.startswith("/device:"):
            continue
        scope_ids = {i for i, n in stat_names.items() if n == SCOPE_STAT}
        table = {}
        for ev in events:
            ev_name, scope = None, None
            for f, v in _fields(ev):
                if f == 2:
                    ev_name = bytes(v).decode()
                elif f == 5:
                    stat = dict(_fields(v))
                    if stat.get(1) in scope_ids:
                        scope = (
                            bytes(stat[5]).decode() if 5 in stat
                            else stat_names.get(stat.get(7))
                        )
            if ev_name is not None and scope is not None:
                table[ev_name] = scope
        out[name] = table
    return out


def planes_from_file(path: str) -> list:
    """``trace.planes_from_file``'s planes, with a ``scopes`` table on
    each device op line: op event name -> scope path (``op_scopes``), one
    entry per distinct op; an op without one is left out. Keyed by the
    event's full text, since short names (``fusion.12``) repeat across
    the programs of one window."""
    planes = trace.planes_from_file(path)
    tables = op_scopes(path)
    for plane in planes:
        table = tables.get(plane["name"])
        if not table:
            continue
        for line in plane["lines"]:
            if line["name"] == trace.OP_LINE:
                names = {ev[0] for ev in line["events"]}
                line["scopes"] = {k: table[k] for k in names if k in table}
    return planes


def _host_spans(planes, prefix):
    return [
        (ev[1], ev[1] + ev[2], ev[0])
        for p in planes
        if p["name"].startswith("/host:")
        for line in p["lines"]
        for ev in line["events"]
        if ev[0].startswith(prefix)
    ]


def _innermost(spans, t):
    open_ = [s for s in spans if s[0] <= t < s[1]]
    return max(open_)[2] if open_ else None


def reduce(planes: list, devices: int, top: int = 10) -> StageSummary:
    """Stage seconds and labelled idle gaps of the first ``devices``
    device planes inside the ``bench.window`` span (see module
    docstring)."""
    bench = _host_spans(planes, trace.SPAN_PREFIX)
    windows = [s for s in bench if s[2] == trace.WINDOW_SPAN]
    if not windows:
        raise ValueError(f"trace has no {trace.WINDOW_SPAN} span")
    w0, w1 = windows[0][0], windows[0][1]
    inner = [s for s in bench if s[2] != trace.WINDOW_SPAN]
    program = _host_spans(planes, PROGRAM_SPAN_PREFIX)
    dev_planes = sorted(
        (p for p in planes if p["name"].startswith("/device:TPU:")),
        key=lambda p: int(p["name"].rsplit(":", 1)[1]),
    )[:devices]
    if not dev_planes:
        raise ValueError("trace has no TPU device plane")
    busy, stage_ns, gaps, unscoped, paths = 0.0, {}, [], {}, {}
    for p in dev_planes:
        ivs = []
        for line in p["lines"]:
            if line["name"] != trace.OP_LINE:
                continue
            scopes = line.get("scopes", {})
            for name, start, dur in line["events"]:
                a, b = max(start, w0), min(start + dur, w1)
                if b <= a:
                    continue
                ivs.append((a, b))
                if not trace.op_name(name).startswith(trace.CONTAINERS):
                    stage = stage_of(scopes.get(name))
                    stage_ns[stage] = stage_ns.get(stage, 0.0) + (b - a)
                    if stage == NONE:
                        unscoped[name] = unscoped.get(name, 0.0) + (b - a)
                        paths[name] = scopes.get(name)
        merged = trace._union(ivs)
        busy += sum(b - a for a, b in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps += [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    n = len(dev_planes)

    def label(a, b):
        mid = (a + b) / 2
        name = _innermost(inner, mid) or trace.WINDOW_SPAN
        prog = _innermost(program, mid)
        return f"{name}/{prog}" if prog else name

    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    return StageSummary(
        window_s=(w1 - w0) / 1e9,
        busy_s=busy / n / 1e9,
        devices=n,
        stages={
            k: v / n / 1e9 for k, v in sorted(stage_ns.items(), key=lambda kv: -kv[1])
        },
        gaps=[[label(a, b), (b - a) / 1e9] for a, b in gaps[:top]],
        unscoped=[
            [op[:200], t / n / 1e9, paths[op]]
            for op, t in sorted(unscoped.items(), key=lambda kv: -kv[1])[:top]
        ],
    )


def measure(cell, seed: int, seconds: float, *, require_tpu=True) -> tuple:
    """Warm the cell's programs, then run its studies for one window with
    the profiler off and one with it on (``run.py``'s window; both
    submit the same sequence of studies from ``seed``). Returns
    ``({"untraced"|"traced": (studies, seconds)}, planes of the traced
    window)``."""
    import shutil
    import tempfile
    import time

    import jax

    from chipbench import graphs, studies
    from chipbench.program import Program

    dev = jax.devices()[0]
    if require_tpu and dev.platform != "tpu":
        raise SystemExit(f"chipbench: no TPU; JAX sees {dev.platform} devices only")
    cfg, traffic = cell.config, cell.traffic
    inputs = cell.kind.inputs(cfg["payload"]) if cell.kind else None
    protocols = list(dict.fromkeys(s["protocol"] for s in traffic["studies"]))
    program = Program(cfg, graphs.make(cfg["graph"]), protocols, cell.kind, inputs)
    for st in studies.warmups(traffic, seed):
        program.fetch(program.dispatch(st))

    def window():
        n, gen, w0 = 0, studies.studies(traffic, seed), time.perf_counter()
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            while time.perf_counter() - w0 < seconds or not n:
                st = next(gen)
                with jax.profiler.TraceAnnotation("bench.dispatch"):
                    outs = program.dispatch(st)
                with jax.profiler.TraceAnnotation("bench.fetch"):
                    program.fetch(outs)
                n += 1
        return n, time.perf_counter() - w0

    windows = {"untraced": window()}
    trace_dir = tempfile.mkdtemp(prefix="chipbench-stages-")
    jax.profiler.start_trace(trace_dir)
    windows["traced"] = window()
    jax.profiler.stop_trace()
    try:
        return windows, planes_from_file(trace.find_xplane(trace_dir))
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)


def main(argv=None) -> int:
    import argparse
    import json

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from chipbench import run as run_mod

    for p in (str(run_mod.ROOT), str(run_mod.ROOT / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    run_mod.use_cache()
    import jax

    from chipbench import spec

    cell = spec.resolve(args.workload)
    windows, planes = measure(cell, args.seed, args.seconds)
    s = reduce(planes, cell.chips)
    kind = jax.devices()[0].device_kind
    per_study = {k: sec / n for k, (n, sec) in windows.items()}
    result = {
        "cell": cell.name, "device": kind,
        "windows": {k: {"studies": n, "window_s": sec} for k, (n, sec) in windows.items()},
        "trace_cost": per_study["traced"] / per_study["untraced"] - 1.0,
        "window_s": s.window_s, "busy_s": s.busy_s, "stages": s.stages,
        "gaps": s.gaps, "unscoped": s.unscoped,
    }
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
