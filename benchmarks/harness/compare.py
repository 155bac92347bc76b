"""The comparison that decides `correct`: every answer the window
received against the plain reference's rows, as numbers each with a
limit of its own.

  rows_differ   answers whose row count, or any value that is not a
                float (keys, dates, strings, counts), differs from the
                reference. Exact: limit 0.
  rows_misordered
                answers whose rows do not come in the statement's
                ORDER BY (`order_by` of its queries/*.json). Exact:
                limit 0. Two floats within max_rel_err's limit of
                each other tie, and the next key decides.
  max_rel_err   the widest relative gap of a float value from the
                reference's, over every float of every answer. The
                limit sits between what sound runs of the program read
                and what the reference computed in float32 reads
                (PERF.md section 2 gives both).
  unanswered    statements that failed, timed out or never answered.
                Limit 0.
"""

from __future__ import annotations

import datetime

_EPOCH = datetime.date(1970, 1, 1)

LIMITS = {"rows_differ": 0, "rows_misordered": 0, "max_rel_err": 1e-9,
          "unanswered": 0}


def wire_rows(columns, data):
    """Client-protocol rows -> python values comparable with the
    reference's (dates travel as ISO strings; the reference keeps int
    days)."""
    is_date = [c["type"] == "date" for c in columns]
    out = []
    for row in data:
        out.append(tuple(
            (datetime.date.fromisoformat(v) - _EPOCH).days
            if d and isinstance(v, str) else v
            for d, v in zip(is_date, row)))
    return out


def _exact_part(row):
    return tuple(str(v) for v in row if not isinstance(v, float))


def answer_gap(got, want):
    """(differs, widest relative float gap) of one answer against the
    reference's rows, as sets (rows are matched by their exact
    columns; `misordered` judges the sequence). A float against a
    null, or a different shape, is a difference."""
    if len(got) != len(want):
        return True, 0.0
    got = sorted((tuple(r) for r in got), key=_exact_part)
    want = sorted((tuple(r) for r in want), key=_exact_part)
    worst = 0.0
    for g, w in zip(got, want):
        if len(g) != len(w):
            return True, worst
        for gv, wv in zip(g, w):
            if isinstance(wv, float) and isinstance(gv, (float, int)) \
                    and not isinstance(gv, bool):
                scale = abs(wv)
                gap = abs(gv - wv) / scale if scale else abs(gv)
                # a NaN compares false everywhere: it must not pass
                worst = max(worst, gap) if gap == gap else float("inf")
            elif gv != wv:
                return True, worst
    return False, worst


def misordered(rows, order_by) -> bool:
    """Whether `rows` break the statement's ORDER BY. `order_by`:
    [[column index, "asc" | "desc"], ...]. The set of rows is
    answer_gap's to judge; this reads only their sequence, so it needs
    no reference."""
    tol = LIMITS["max_rel_err"]

    def in_order(a, b):
        for col, direction in order_by:
            x, y = a[col], b[col]
            if isinstance(x, float) or isinstance(y, float):
                if abs(x - y) <= tol * max(abs(x), abs(y)):
                    continue
            elif x == y:
                continue
            return (x < y) == (direction == "asc")
        return True

    return not all(in_order(a, b) for a, b in zip(rows, rows[1:]))


def judge(answers, reference_rows, unanswered: int, order_by: dict):
    """`answers`: [(statement name, rows)], the rows as wire_rows gives
    them; `reference_rows`: {statement name: rows}; `order_by`:
    {statement name: its ORDER BY keys, or nothing}. Returns
    ({number: {"value", "limit"}}, correct, per-answer verdicts)."""
    seen: dict = {}
    verdicts = []
    differ = 0
    disorder = 0
    worst = 0.0
    for name, rows in answers:
        key = (name, repr(rows))
        if key not in seen:
            seen[key] = (*answer_gap(rows, reference_rows[name]),
                         misordered(rows, order_by.get(name) or []))
        bad, gap, shuffled = seen[key]
        differ += bad
        disorder += shuffled
        worst = max(worst, gap)
        verdicts.append(not bad and not shuffled
                        and gap <= LIMITS["max_rel_err"])
    numbers = {
        "rows_differ": {"value": differ, "limit": LIMITS["rows_differ"]},
        "rows_misordered": {"value": disorder,
                            "limit": LIMITS["rows_misordered"]},
        "max_rel_err": {"value": worst, "limit": LIMITS["max_rel_err"]},
        "unanswered": {"value": unanswered,
                       "limit": LIMITS["unanswered"]},
    }
    correct = bool(answers) and all(
        n["value"] <= n["limit"] for n in numbers.values())
    return numbers, correct, verdicts
