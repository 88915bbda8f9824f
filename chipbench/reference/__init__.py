"""Plain references: the same studies recomputed without the program.

``replay`` gives the per-round outputs of one trajectory of a study, as
the configuration states it should run; ``precision="bfloat16"`` gives
the control, the same reference one precision step down.
"""
from __future__ import annotations


def replay(config: dict, neighbors, study, index: int, task=None, precision="float32"):
    from chipbench.spec import protocol

    proto = protocol(config, study.protocol)
    if config.get("payload"):
        from chipbench.reference import rwsgd

        return rwsgd.trajectory(
            neighbors, proto, config, study.base_key, study.seeds, index, task, precision
        )
    from chipbench.reference import walks

    return walks.trajectory(
        neighbors, proto, config["failures"], config["steps"],
        study.base_key, study.seeds, index, precision,
    )
