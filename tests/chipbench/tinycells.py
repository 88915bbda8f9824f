"""Tiny versions of the benchmark's cells, for runs on the CPU: the same
rules, configuration keys and traffic, on a small graph, a short horizon
and a one-layer model."""
import copy
import dataclasses

from chipbench import spec


def shrink(cell):
    cfg = copy.deepcopy(cell.config)
    cfg["graph"] = {"family": "random_regular", "n": 24, "degree": 4, "seed": 0}
    if cfg.get("payload"):
        cfg.update(
            steps=40,
            protocol={"z0": 3, "max_walks": 6, "rt_bins": 64, "protocol_start": 10},
            failures={"burst_times": [25], "burst_sizes": [2]},
        )
        cfg["payload"]["model"].update(
            num_layers=1, d_model=32, d_ff=64, vocab_size=64,
            num_heads=2, num_kv_heads=1, head_dim=16,
        )
    else:
        cfg.update(
            steps=2500,
            protocol={"z0": 4, "max_walks": 16, "rt_bins": 64, "protocol_start": 100},
            failures={"burst_times": [300, 1500], "burst_sizes": [2, 2]},
        )
    return dataclasses.replace(cell, config=cfg, traffic=dict(cell.traffic, seeds=4))


def tiny(name):
    return shrink(spec.resolve(name))
