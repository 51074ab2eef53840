"""The knee of an open-loop cell is read from its sweep by one stated rule."""

from __future__ import annotations

import pytest

from benchmarks.chip import knee


def _row(rate, second, last, unanswered=0):
    # the first quarter, which arrives at an idle service, is not compared
    return {"offered_rps": rate, "quarter_p50_ms": [1.0, second, second, last],
            "unanswered": unanswered}


@pytest.mark.parametrize("row, holds", [
    (_row(16, 500.0, 500.0 * knee.GROWTH), True),
    (_row(16, 500.0, 400.0), True),
    (_row(16, 500.0, 500.0 * knee.GROWTH + 1.0), False),
    (_row(16, 500.0, 400.0, unanswered=1), False),
])
def test_a_window_holds_while_its_queue_does_not_grow(row, holds):
    assert knee.holds(row) is holds


def test_the_knee_is_the_rate_below_the_first_window_that_fails():
    rows = [_row(8, 400, 410), _row(8, 400, 390),
            _row(16, 500, 520), _row(16, 500, 700),  # one repeat fails
            _row(24, 600, 610)]  # passes, but lies above a failure
    assert knee.knee_of(rows) == 8
    assert knee.knee_of(rows[:2]) == 8
    assert knee.knee_of([_row(8, 400, 900)]) == 0.0
