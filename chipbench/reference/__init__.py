"""Plain references: the same studies recomputed without the program.

``replay`` gives the per-round outputs of one trajectory of a study, as
the configuration states it should run; ``precision="bfloat16"`` gives
the control, the same reference one precision step down. A configuration
of walks alone replays through ``walks.trajectory``; one with a learning
payload through the ``trajectory`` of its kind's ``reference/<kind>.py``
(``payloads.of``).
"""
from __future__ import annotations


def replay(config: dict, neighbors, study, index: int, kind=None, inputs=None,
           precision="float32"):
    """``kind``: ``payloads.of(config)``; ``inputs``: what its ``inputs``
    made from the configuration's ``payload`` entry."""
    from chipbench.spec import protocol

    proto = protocol(config, study.protocol)
    if config.get("payload"):
        return kind.trajectory(
            neighbors, proto, config, study.base_key, study.seeds, index, inputs, precision
        )
    from chipbench.reference import walks

    return walks.trajectory(
        neighbors, proto, config["failures"], config["steps"],
        study.base_key, study.seeds, index, precision,
    )
