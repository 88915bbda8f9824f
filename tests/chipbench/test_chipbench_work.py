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
