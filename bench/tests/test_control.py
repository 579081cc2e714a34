"""The control, the reference computed in float8 in the program's place,
reads above the limits that the program's bfloat16 stays under."""
import pytest

from benchlib.cell import Cell, checks_pass
from tiny import TINY_TRAFFIC, tiny_config


@pytest.mark.parametrize("seed", [11, 2 ** 31 + 5, 987654321])
def test_control_fails_where_the_program_passes(seed):
    config = tiny_config()
    cell = Cell(config, TINY_TRAFFIC, seed)
    cell.setup()
    win = cell.window(1.5)
    cell.close()
    limits = config["check"]["limits"]
    assert checks_pass(cell.check(win, limits))
    assert not checks_pass(cell.check(win, limits, "fp8"))
