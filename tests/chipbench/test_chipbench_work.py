"""Operation and byte counts against hand-worked numbers."""
import json
from pathlib import Path

import numpy as np

from chipbench import work

CONFIGS = Path(__file__).resolve().parents[2] / "chipbench" / "configs"


def test_walk_round_bytes_of_the_paper_cell():
    cfg = json.loads((CONFIGS / "paper-regular100.json").read_text())
    # 1024 int16 return-time bins + 64 int32 last-seen entries
    assert work.walk_round_bytes(cfg) == 2 * 1024 + 4 * 64 == 2304


def test_walk_round_bytes_trims_bins_to_the_run():
    cfg = {"steps": 300, "protocol": {"rt_bins": 512, "max_walks": 16}}
    assert work.walk_round_bytes(cfg) == 2 * 300 + 4 * 16


def test_active_walk_rounds_sums_z():
    outs = [(None, {"z": np.array([[3, 4], [5, 6]])}), (None, {"z": np.array([[1, 1]])})]
    assert work.active_walk_rounds(outs) == 20


def test_transformer_step_flops_of_paper_rwsgd():
    cfg = json.loads((CONFIGS / "rwsgd-regular100.json").read_text())["payload"]
    # per layer: q 256*8*32 + k,v 2*256*4*32 + o 8*32*256 + SwiGLU 3*256*1024
    per_layer = 65536 + 65536 + 65536 + 786432
    matmul = 2 * (4 * per_layer + 256 * 4096) * 2 * 32  # 637,534,208
    attn = 2 * 8 * 32 * 32 * 33 * 4 * 2  # causal scores and values: 4,325,376
    assert matmul == 637_534_208 and attn == 4_325_376
    flops = work.transformer_step_flops(cfg["model"], cfg["local_batch"], cfg["seq_len"])
    assert flops == 3 * (matmul + attn) == 1_925_578_752
