"""The control, the reference at fp8, fails the cell's limits.

At a tiny size on the CPU: the reference computed with every matrix
multiplication's inputs rounded to 8-bit floats is compared with the
float32 reference as a run compares the program, under each cell's limits.
"""

import pytest

import check as C
from tiny import NAMES, tiny_cell

SEEDS = (11, 2**31 + 7)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", NAMES)
def test_control_is_not_correct(name, seed):
    cell = tiny_cell(name)
    ref = C.reference_readings(cell, seed)
    ctl = C.reference_readings(cell, seed, precision="fp8")
    numbers = C.compare(ctl, ref, cell.check)
    assert any(n["value"] > n["limit"] for n in numbers.values()), numbers
