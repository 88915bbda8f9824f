"""BENCHMARK.json resolves, by name, to files that exist and agree."""
import json
import re
from pathlib import Path

import pytest

from chipbench import spec
from chipbench.run import peaks_for

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves_to_its_files(cell):
    c = spec.resolve(cell)
    entry = next(w for w in BENCH["workloads"] if w["name"] == cell)
    assert c.config["name"] == entry["config"]
    assert {s["protocol"] for s in c.traffic["studies"]} <= set(c.config["protocols"])
    e2e = {m.name for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer
    assert all(callable(m.read) for m in c.end_to_end + c.per_layer)
    assert c.config["limits"]


def test_contract_shape():
    assert set(BENCH) == {
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"
    }
    for p in BENCH["paths"]:
        assert (ROOT / p).is_dir()
    for word in BENCH["command"][1:]:
        assert any(word.startswith(p + "/") for p in BENCH["paths"])
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer") for x in BENCH[k]]
    assert all(NAME.match(n) for n in names) and len(names) == len(set(names))
    for c in BENCH["configs"]:
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
        assert c["reduced"] == json.loads((ROOT / c["file"]).read_text())["reduced"]
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        for w in m.get("workloads", ()):
            assert e2e[m["moves"]].get("workloads") is None or w in e2e[m["moves"]]["workloads"]


def test_unknown_device_kind_is_an_error():
    assert peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SystemExit, match="no peaks"):
        peaks_for("TPU v99")
