"""A run with its timed path broken underneath comes out not correct.

Each test drives the harness's whole run (set-up, window, reference,
comparison, with the cell's own limits) on the CPU at a tiny size, past
the look for a chip, with one fault of bench/faults.py planted.
"""

import pytest

import faults as F
import run as R
from tiny import NAMES, tiny_cell

SEED = 2**31 + 4242


def _run(cell):
    return R.run(cell, SEED, 0.3, False, require_chip=False)


@pytest.mark.parametrize("name", NAMES)
def test_sound_run_is_correct(name):
    res = _run(tiny_cell(name))
    assert res["correct"], res["check"]


@pytest.mark.parametrize("fault", ["frozen_state", "half_batch",
                                   "altered_loss"])
@pytest.mark.parametrize("name", NAMES)
def test_fault_is_caught(name, fault):
    with F.plant(fault):
        res = _run(tiny_cell(name))
    assert not res["correct"], res["check"]


def test_left_out_exchange_is_caught():
    with F.plant("no_exchange"):
        res = _run(tiny_cell("gpt2s-4chip-g4-h10"))
    assert not res["correct"], res["check"]
    assert res["check"]["change_gap"]["value"] > \
        res["check"]["change_gap"]["limit"], res["check"]
