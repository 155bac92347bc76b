"""The sequence half of the comparison: `misordered` reads an
answer's rows against its statement's ORDER BY."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.harness import compare  # noqa: E402

Q3 = [[1, "desc"], [2, "asc"]]     # revenue desc, orderdate


@pytest.mark.parametrize("rows, order_by, bad", [
    ([(1, 9.0, 5), (2, 8.0, 1)], Q3, False),
    ([(2, 8.0, 1), (1, 9.0, 5)], Q3, True),
    # equal floats: the next key decides
    ([(1, 9.0, 5), (2, 9.0, 6)], Q3, False),
    ([(1, 9.0, 6), (2, 9.0, 5)], Q3, True),
    # floats within the limit of each other tie, whichever is larger
    ([(1, 9.0, 5), (2, 9.0 * (1 + 1e-12), 6)], Q3, False),
    ([(1, 9.0, 6), (2, 9.0 * (1 - 1e-12), 5)], Q3, True),
    # beyond the limit they do not
    ([(1, 9.0, 5), (2, 9.0 * (1 + 1e-6), 6)], Q3, True),
    ([("A", "F"), ("N", "F"), ("N", "O"), ("R", "F")],
     [[0, "asc"], [1, "asc"]], False),
    ([("A", "F"), ("N", "O"), ("N", "F"), ("R", "F")],
     [[0, "asc"], [1, "asc"]], True),
    ([(3.0,)], [], False),
    ([(2, 1.0), (1, 2.0)], [], False),   # no ORDER BY: any sequence
])
def test_misordered(rows, order_by, bad):
    assert compare.misordered(rows, order_by) is bad


def test_reordered_answer_fails_one_number_only():
    want = {"q": [(1, 9.0, 5), (2, 8.0, 1)]}
    numbers, correct, verdicts = compare.judge(
        [("q", want["q"][::-1])], want, 0, {"q": Q3})
    assert correct is False and verdicts == [False]
    assert numbers["rows_misordered"]["value"] == 1
    assert numbers["rows_differ"]["value"] == 0
    numbers, correct, verdicts = compare.judge(
        [("q", want["q"])], want, 0, {"q": Q3})
    assert correct is True and verdicts == [True]
