"""The control at a small size on the CPU: the reference put in the
program's place at the configuration's stated precision is correct, and
one precision below it is not, in every kind of cell."""
import pytest

from bench import control
from bench.tests.small import run_small


@pytest.mark.parametrize("cell", ["chip64.batch", "gset2000.fabric",
                                  "chip64.stream"])
@pytest.mark.parametrize("precision,correct",
                         [("bfloat16", True),
                          (control.CONTROL_PRECISION, False)])
def test_control(cell, precision, correct):
    restore = control.install(precision)
    try:
        res = run_small(cell)
    finally:
        restore()
    assert res["correct"] is correct, res["checks"]
