"""The control (the reference in the precision below the configuration's,
in the program's place) against the sound program at a tiny size on the
CPU: it reads at least three times the program's reading on a number of
the comparison, so that a limit set between the two separates them."""
import pytest

from bench import control
from bench.tests.test_bench_rehearsal import TINY

SEPARATING = ("kernel_gap", "decision_gap")


@pytest.mark.parametrize("cell", sorted(TINY))
def test_control_reads_apart_from_the_program(cell):
    cfg, traffic = TINY[cell]
    seed = 2 ** 31 + 7
    prog = control.readings(cell, seed, False, cfg, traffic)
    ctrl = control.readings(cell, seed, True, cfg, traffic)
    assert ctrl["rank_gap"] == prog["rank_gap"] == 0
    assert ctrl["label_mismatch"] == 0
    assert max(ctrl[k] / prog[k] for k in SEPARATING) >= 3.0, (prog, ctrl)
