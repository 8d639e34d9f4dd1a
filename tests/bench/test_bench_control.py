"""The lower-precision control (the plain reference computed in bfloat16
in the program's place) comes out not correct, at a size a CPU test run
can hold.  `bench/control.py` reads the same numbers at the cells' own
sizes on the chip."""
from __future__ import annotations

import pytest

from bench import check, control
from bench_cells import cell_for, tiny


@pytest.mark.parametrize("op,gen", [("compress", "hurricane"),
                                    ("compress", "hacc"),
                                    ("decompress", "hurricane"),
                                    ("decompress", "hacc")])
def test_control_fails_the_comparison(op, gen):
    nums = control.control_numbers(tiny(cell_for(op, gen)), 2 ** 31 + 5)
    verdicts = check.verdict(nums)
    assert not all(v["ok"] for v in verdicts.values())
    # the control reads at least three times the limit it must fail
    assert nums["err_over_bound"] > 3 * check.LIMITS["err_over_bound"]
