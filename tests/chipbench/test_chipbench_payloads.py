"""Learning payloads found by kind: the ``rwsgd`` kind hands the program
and the metrics what the configuration states, and a kind kept outside
``chipbench/`` runs a whole cell through the loader's directories."""
import dataclasses
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest
from tinycells import tiny

from chipbench import payloads, run, spec, trace
from chipbench.record import Record

ROOT = Path(__file__).resolve().parents[2]
CONFIGS = ROOT / "chipbench" / "configs"
DATA = Path(__file__).with_name("data")
LEARN = "rwsgd-regular100.learn"


def _config(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def sgd_cell():
    """The tiny learn cell with its payload of the fixture kind ``sgd``
    (``data/payloads/sgd.py``, ``data/reference/sgd.py``)."""
    cell = tiny(LEARN)
    cell.config["payload"].update(kind="sgd", optimizer={"name": "sgd", "lr": 0.05})
    kind = payloads.of(cell.config, DATA / "payloads", DATA / "reference")
    return dataclasses.replace(cell, kind=kind)


def test_transformer_step_flops_of_paper_rwsgd():
    cfg = _config("rwsgd-regular100")["payload"]
    # per layer: q 256*8*32 + k,v 2*256*4*32 + o 8*32*256 + SwiGLU 3*256*1024
    per_layer = 65536 + 65536 + 65536 + 786432
    matmul = 2 * (4 * per_layer + 256 * 4096) * 2 * 32  # 637,534,208
    attn = 2 * 8 * 32 * 32 * 33 * 4 * 2  # causal scores and values: 4,325,376
    assert matmul == 637_534_208 and attn == 4_325_376
    flops = payloads.load("rwsgd").step_flops(cfg)
    assert flops == 3 * (matmul + attn) == 1_925_578_752


def test_a_payload_without_a_kind_is_rwsgd_and_walks_alone_have_none():
    cfg = _config("rwsgd-regular100")
    assert "kind" not in cfg["payload"]
    assert payloads.of(cfg).name == "rwsgd"
    assert payloads.of(_config("paper-regular100")) is None
    assert spec.resolve(LEARN).kind.name == "rwsgd"
    assert spec.resolve("paper-regular100.fig1-decafork").kind is None


def test_rwsgd_inputs_are_the_rank_16_markov_table_bitwise():
    p = _config("rwsgd-regular100")["payload"]
    rng = np.random.default_rng(1234)
    u = rng.standard_normal((4096, 16))
    v = rng.standard_normal((16, 4096))
    want = (u @ v / np.sqrt(16) * 2.0).astype(np.float32)
    got = payloads.load("rwsgd").inputs(p)
    assert got.dtype == np.float32 and got.shape == (4096, 4096)
    assert np.array_equal(got.view(np.uint32), want.view(np.uint32))


def test_rwsgd_build_is_the_configured_payload():
    from repro.models.config import ModelConfig
    from repro.optim import RwSgdPayload, adamw

    cfg = _config("rwsgd-regular100")
    p, o = cfg["payload"], cfg["payload"]["optimizer"]
    kind = payloads.of(cfg)
    task = kind.inputs(p)
    pay = kind.build(cfg, task)
    assert type(pay) is RwSgdPayload
    assert pay.model.cfg == ModelConfig(**p["model"])
    assert pay.optimizer.signature == adamw(o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"]).signature
    assert np.array_equal(np.asarray(pay.task.logits), task)
    assert (pay.max_walks, pay.local_batch, pay.seq_len, pay.train_every) == (16, 2, 32, 1)


def test_an_unknown_kind_names_the_files_it_looked_for():
    cfg = _config("rwsgd-regular100")
    cfg["payload"]["kind"] = "sgd"  # exists only under tests/chipbench/data
    with pytest.raises(payloads.UnknownKind) as e:
        payloads.of(cfg)
    msg = str(e.value)
    assert "'sgd'" in msg
    assert str(ROOT / "chipbench" / "payloads" / "sgd.py") in msg
    assert str(ROOT / "chipbench" / "reference" / "sgd.py") in msg


def test_a_kind_outside_chipbench_runs_a_whole_cell():
    """The fixture kind's inputs, builder and reference drive a whole run
    on the CPU: its program trains by SGD, which only its own reference
    replays (``rwsgd``'s is AdamW's)."""
    cell = sgd_cell()
    assert cell.kind.name == "sgd"
    res = run.run(cell, 2**31 + 29, 0.1, False, require_tpu=False, t0=time.perf_counter())
    assert res["correct"], res["checks"]
    checks = res["checks"]
    assert checks["mismatch_rounds"]["value"] == 0
    assert checks["fork_copy_gap"]["value"] == 0.0
    assert checks["loss_rel_gap"]["value"] < 1e-5
    assert set(res["metrics"]) == {"setup_s", "replica_steps_per_s"}


def test_payload_mfu_reads_the_kind_s_step_flops():
    cell = sgd_cell()
    p = cell.config["payload"]
    read = spec.reader("payload_mfu")
    summary = trace.Summary(window_s=2.0, busy_s=1.5, devices=1, ops=[], gaps=[])
    rec = Record(
        config=cell.config, setup_s=1.0, window_s=2.0,
        studies=[(None, {"trained": np.array([[3, 4], [5, 0]])})],
        compile={}, peaks={"bf16_flops_per_s": 1e12}, trace=summary, kind=cell.kind,
    )
    assert cell.kind.step_flops(p) != payloads.load("rwsgd").step_flops(p)
    assert read(rec) == pytest.approx(100.0 * 12 * cell.kind.step_flops(p) / (2.0 * 1e12))
    rec.kind = None
    assert read(rec) is None


def test_the_rwsgd_reference_and_kind_import_nothing_of_the_program():
    code = (
        "import importlib.util, sys\n"
        "import chipbench.reference.rwsgd\n"
        "from chipbench import payloads\n"
        "payloads.load('rwsgd')\n"
        "assert importlib.util.find_spec('repro') is not None\n"
        "print(sorted(m for m in sys.modules if m.split('.')[0] == 'repro'))\n"
    )
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": os.pathsep.join([str(ROOT), str(ROOT / "src")])}
    r = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip() == "[]"
