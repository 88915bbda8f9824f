"""Learning payloads by kind: each kind is two files, found by its name.

A configuration's ``payload`` entry names its kind (``payload["kind"]``;
``"rwsgd"`` where the key is absent). The kind's files are:

- ``payloads/<kind>.py``, the benchmark's side of the program, and the
  one file of a kind that imports ``repro`` (inside its functions):
  ``inputs(payload)`` makes the learning task's host arrays from the
  ``payload`` entry; ``build(config, inputs)`` the program's payload
  object over them; ``step_flops(payload)`` counts the FLOPs of one
  replica's local step; ``faults()`` gives the faults a run can plant in
  the payload, ``{name: [(object, attribute, replacement), ...]}``
  (``faults.py``). The payload's outputs carry ``loss`` (rounds, W) and
  ``trained`` (rounds,).
- ``reference/<kind>.py``, the plain reference, which imports nothing of
  the program: ``trajectory(neighbors, proto, config, base_key, seeds,
  index, inputs, precision)`` replays one trajectory as
  ``reference.replay`` documents, with ``precision`` one of
  ``"float32"``, ``"bfloat16"`` (everything one step down) and
  ``"bfloat16-payload"`` (the payload alone).

Adding a kind is adding these two files, a configuration that names it,
a traffic mix and entries in ``BENCHMARK.json``; nothing here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import sys
from pathlib import Path
from typing import Callable

HERE = Path(__file__).resolve().parent
REFERENCES = HERE.parent / "reference"
DEFAULT = "rwsgd"


class UnknownKind(LookupError):
    """No files for a payload kind."""


@dataclasses.dataclass(frozen=True)
class Kind:
    name: str
    inputs: Callable  # (payload entry) -> host arrays of the task
    build: Callable  # (config, inputs) -> the program's payload object
    step_flops: Callable  # (payload entry) -> FLOPs of one replica step
    faults: Callable  # () -> {fault: [(object, attribute, replacement)]}
    trajectory: Callable  # the reference's replay of one trajectory


def _module(path: Path):
    """The module of the file at ``path``, executed once per process."""
    name = f"chipbench_kind:{path}"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        sys.modules[name] = mod
    return sys.modules[name]


def load(kind: str, payloads: Path = HERE, references: Path = REFERENCES) -> Kind:
    """The kind's functions, from ``<payloads>/<kind>.py`` and
    ``<references>/<kind>.py``."""
    files = (Path(payloads) / f"{kind}.py", Path(references) / f"{kind}.py")
    missing = [str(f) for f in files if not f.is_file()]
    if missing:
        raise UnknownKind(
            f"no payload kind {kind!r}: looked for {files[0]} and {files[1]}, "
            f"missing {', '.join(missing)}"
        )
    side, ref = (_module(f) for f in files)
    return Kind(
        name=kind, inputs=side.inputs, build=side.build, step_flops=side.step_flops,
        faults=side.faults, trajectory=ref.trajectory,
    )


def of(config: dict, payloads: Path = HERE, references: Path = REFERENCES) -> Kind | None:
    """The kind of ``config``'s payload; None for a configuration of walks
    alone."""
    p = config.get("payload")
    if not p:
        return None
    return load(p.get("kind", DEFAULT), payloads, references)
