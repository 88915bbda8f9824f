"""The plain references replay the program exactly, and their
lower-precision controls are rejected by the check."""
import pytest
from tinycells import tiny

from chipbench import check, graphs, reference, studies
from chipbench.program import Program

SWEEP = "paper-regular100.fig1-decafork"
LEARN = "rwsgd-regular100.learn"
SEEDS = 4


def _study(cell, proto):
    cfg = cell.config
    neighbors = graphs.make(cfg["graph"])
    task = cell.kind.inputs(cfg["payload"]) if cell.kind else None
    program = Program(cfg, neighbors, [proto], cell.kind, task)
    st = studies.Study(0, proto, SEEDS, 2**31 + 17)
    return neighbors, task, st, program.fetch(program.dispatch(st))


@pytest.mark.parametrize(
    "name,proto",
    [(SWEEP, "decafork"), (SWEEP, "decafork+"), (SWEEP, "missingperson"), (LEARN, "decafork")],
)
def test_reference_replays_the_program_and_rejects_its_control(name, proto):
    cell = tiny(name)
    cfg = cell.config
    neighbors, task, st, host = _study(cell, proto)
    for i in range(SEEDS):
        prog = {k: v[i] for k, v in host.items()}
        ref = reference.replay(cfg, neighbors, st, i, cell.kind, task)
        nums = check.combine([check.compare_trajectory(prog, ref)])
        assert nums["mismatch_rounds"] == 0
        assert nums["compared_rounds"] == cfg["steps"] or nums["ties"] == 1
        for gap in ("theta_mean_rel_gap", "loss_rel_gap", "first_loss_median_gap"):
            assert nums.get(gap, 0.0) < 1e-5
        assert nums.get("fork_copy_gap", 0.0) == 0.0
        assert check.verdict(nums, cfg["limits"])[0]
    # the controls: the reference one precision step down, in the program's
    # place; for the payload also the model alone, theta as stated
    for precision in ("bfloat16", "bfloat16-payload") if task is not None else ("bfloat16",):
        ctl = [
            check.compare_trajectory(
                reference.replay(cfg, neighbors, st, i, cell.kind, task, precision=precision),
                reference.replay(cfg, neighbors, st, i, cell.kind, task),
            )
            for i in range(SEEDS)
        ]
        correct, compared = check.verdict(check.combine(ctl), cfg["limits"])
        assert not correct, (precision, compared)
