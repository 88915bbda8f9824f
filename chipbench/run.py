"""Run one benchmark cell on the chip and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up builds the program's plans for the cell's configuration and runs
one warm-up study of every protocol the traffic submits (the normal entry
has no way to warm a program but to run it), so every program is compiled
or loaded from the persistent cache before the window. The window then
submits the traffic's studies back to back; a study counts once its
outputs are on the host, and the window closes at the first study
boundary after ``--seconds``. A program built inside the window fails the
run. Afterwards the reference replays a sample of the window's
trajectories and decides ``correct``.

``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1`` traces
the window with the profiler and prints its per-layer metrics, the device
busy time, a breakdown and the bytes each compiled program holds
(``program_bytes``: arguments, outputs and temporaries, read from the
executable after the window, since the runtime's peak leaves the
program's temporaries out). The last line of stdout is the JSON result;
the last lines of stderr are the numbers compared, each beside its limit.
Without a TPU, or with fewer chips than the cell asks for, the run exits
non-zero before any work and prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
# fixed, inside the checkout: the path is part of the cache's key
CACHE_DIR = ROOT / ".chipbench_cache" / "jax"
CACHE_BYTES = 2**30


def use_cache():
    """Point JAX's persistent compilation cache at ``CACHE_DIR`` for every
    program, with room for the largest (the learning cell's program, which
    holds the 64 MB task table, serializes to about 190 MB)."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_compilation_cache_max_size", CACHE_BYTES)


def _log(*parts):
    print(*parts, file=sys.stderr, flush=True)


def peaks_for(kind: str) -> dict:
    with open(ROOT / "chipbench" / "peaks.json") as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise SystemExit(f"chipbench: no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def run(cell, seed: int, seconds: float, trace: bool, *, require_tpu=True, t0=None) -> dict:
    """Measure ``cell``; returns the result object (see module docstring).
    ``require_tpu=False`` skips the look for a chip (tests on the CPU)."""
    import jax

    import repro  # noqa: F401  the system under test; without it, no run
    from chipbench import check, graphs, studies
    from chipbench import trace as trace_mod
    from chipbench.clock import CompileClock
    from chipbench.program import Program
    from chipbench.record import Record

    t0 = T0 if t0 is None else t0
    devices = jax.devices()[: cell.chips]
    dev = devices[0]
    peaks = None
    if require_tpu:
        if dev.platform != "tpu":
            raise SystemExit(f"chipbench: no TPU; JAX sees {dev.platform} devices only")
        if len(devices) < cell.chips:
            raise SystemExit(f"chipbench: cell needs {cell.chips} chips, JAX sees {len(devices)}")
        peaks = peaks_for(dev.device_kind)
    _log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; jax {jax.__version__}")

    clock = CompileClock()
    cfg, traffic = cell.config, cell.traffic
    neighbors = graphs.make(cfg["graph"])
    inputs = cell.kind.inputs(cfg["payload"]) if cell.kind else None
    protocols = list(dict.fromkeys(s["protocol"] for s in traffic["studies"]))
    program = Program(cfg, neighbors, protocols, cell.kind, inputs)
    for st in studies.warmups(traffic, seed):
        program.fetch(program.dispatch(st))
    setup_compile = clock.take()
    setup_s = time.perf_counter() - t0

    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    if trace:
        jax.profiler.start_trace(trace_dir)
    done = []
    gen = studies.studies(traffic, seed)
    w0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        while time.perf_counter() - w0 < seconds or not done:
            st = next(gen)
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                outs = program.dispatch(st)
            with jax.profiler.TraceAnnotation("bench.fetch"):
                host = program.fetch(outs)
            del outs
            done.append((st, host))
    window_s = time.perf_counter() - w0
    if trace:
        jax.profiler.stop_trace()
    built = clock.take()
    if built:
        raise SystemExit(f"chipbench: programs were built inside the window: {built}")
    stats = [d.memory_stats() or {} for d in devices]
    memory_peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    # what each program holds while it runs (arguments, outputs and the
    # temporaries, the scan carry among them), which the runtime's peak
    # above leaves out; read from the compiled executables, traced runs only
    program_bytes = (
        {st.protocol: program.compiled_bytes(st) for st in studies.warmups(traffic, seed)}
        if trace else None
    )
    del program

    summary = None
    if trace:
        try:
            summary = trace_mod.reduce(
                trace_mod.planes_from_file(trace_mod.find_xplane(trace_dir)), cell.chips
            )
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)

    # the reference, after the window and after the memory peak was read
    t_ref = time.perf_counter()
    numbers, n_ref = judge(cell, seed, done, neighbors, inputs)
    correct, compared = check.verdict(numbers, cfg["limits"])
    ref_s = time.perf_counter() - t_ref

    rec = Record(
        config=cfg, setup_s=setup_s, window_s=window_s, studies=done,
        compile=setup_compile, peaks=peaks, trace=summary, kind=cell.kind,
    )
    metrics = {}
    for m in cell.per_layer if trace else cell.end_to_end:
        value = m.read(rec)
        if value is None:
            if not trace:
                raise SystemExit(f"chipbench: end-to-end metric {m.name} read nothing")
            continue
        metrics[m.name] = {"value": float(value), "unit": m.unit}

    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(devices),
        "memory_peak_bytes": memory_peak,
    }
    result = {
        "correct": correct, "attempted": len(done), "failed": 0, "metrics": metrics,
        "device": device,
    }
    if summary is not None:
        device.update(
            busy_s=summary.busy_s, window_s=summary.window_s, program_bytes=program_bytes
        )
        result["breakdown"] = {"device_ops": summary.ops, "idle_gaps": summary.gaps}
    result["checks"] = compared

    _log("set-up program building: " + ", ".join(
        f"{e.rsplit('/', 1)[1]} {n}x {sec:.3f} s" for e, (n, sec) in setup_compile.items()
    ))
    _log(
        f"studies in window: {len(done)} in {window_s:.3f} s; set-up {setup_s:.3f} s; "
        f"reference: {n_ref} trajectories in {ref_s:.3f} s; "
        f"ties {numbers.get('ties', 0)}, rounds compared {numbers.get('compared_rounds', 0)}"
    )
    if program_bytes:
        _log(f"program bytes: {program_bytes}; runtime peak {memory_peak}")
    for name, c in compared.items():
        _log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    return result


def judge(cell, seed: int, done: list, neighbors, inputs) -> tuple:
    """The compared numbers of the finished studies ``done`` (pairs of
    study and outputs on the host): the reference replays the trajectories
    ``studies.sample`` draws from ``seed``, over the payload's ``inputs``.
    Returns ``(numbers, count)``."""
    from chipbench import check, reference, studies

    per_traj = []
    by_index = {st.index: host for st, host in done}
    for st, i in studies.sample(cell.traffic, seed, [st for st, _ in done]):
        ref = reference.replay(cell.config, neighbors, st, i, cell.kind, inputs)
        prog = {k: v[i] for k, v in by_index[st.index].items()}
        per_traj.append(check.compare_trajectory(prog, ref))
    return check.combine(per_traj), len(per_traj)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    for p in (str(ROOT), str(ROOT / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    use_cache()
    from chipbench import spec

    cell = spec.resolve(args.workload)
    result = run(cell, args.seed, args.seconds, bool(args.trace))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
