"""The control of the output check comes out as not correct: the plain
reference in float32, judged as the program's answers are, fails a
number of every cell (at sf0_01, a size a test run can hold)."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import control  # noqa: E402

from benchmarks.harness.files import read_json  # noqa: E402


def _queries(names):
    return {n: read_json("queries", f"{n}.json") for n in names}


@pytest.mark.parametrize("seed", [11, 3000000011, 5])
@pytest.mark.parametrize("names", [("q3",), ("q6", "q1")])
def test_float32_reference_is_not_correct(names, seed):
    numbers, correct, per = control.control_numbers(
        0.01, seed, _queries(names))
    assert correct is False
    failing = [n for n, v in numbers.items() if v["value"] > v["limit"]]
    assert failing, numbers
    # the control has to fail one of the cell's numbers, not each
    # statement: q6's float32 sum (accumulated in double by Acero)
    # reads under the limit on its own, q1 and q3 do not
    assert any(differs or gap > numbers["max_rel_err"]["limit"]
               for differs, gap in per.values())
