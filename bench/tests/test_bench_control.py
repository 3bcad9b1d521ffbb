"""The control, the reference computed in bfloat16 in place of the
program, fails at least one check of every cell, and so does every
fault a driver plants in the reference put in the program's place,
while the program passes all of them against the float32 reference:
`readings.py`, whose chip readings set each limit, at a tiny size."""

import pytest

import readings
from benchtest import CELLS, CPU



@pytest.mark.parametrize("name", CELLS)
def test_control_fails_and_program_passes(tiny, name):
    r = readings.readings(tiny, name, 2**31 + 99, 0.01, device=CPU)
    limits = tiny.cell(name)["limits"]
    assert all(v <= limits[k] for k, v in r["sound"].items()), r
    for reading in set(r) - {"seed", "sound"}:
        assert any(v > limits[k] for k, v in r[reading].items()), \
            (reading, r)
