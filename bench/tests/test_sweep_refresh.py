"""The refresh sweep's readings: the backlog before each insert, whether a
rate is sustained, and the knee of a ladder."""
import numpy as np
import pytest

from bench import drive
from bench.sweep_refresh import backlog, knee, sustained


def test_backlog_counts_queries_due_and_unanswered():
    rec = drive.Record(4)
    rec.due[:] = [0.0, 1.0, 2.0, 3.0]
    rec.done[:] = [0.5, 2.5, np.nan, 3.1]          # query 2 never answered
    assert backlog(rec, [0.25, 1.5, 2.75, 10.0]) == [1, 1, 1, 1]
    assert backlog(rec, [2.2]) == [2]


@pytest.mark.parametrize("completed, backlogs, want", [
    (23_880, [40, 35, 60], True),     # kept up; backlog within the floor
    (23_000, [40, 35, 60], False),    # completions fell behind
    (23_880, [90, 150, 400], False),  # backlog grew across the window
    (23_880, [90, 150, 170], True),   # grew, but less than twice the first
])
def test_sustained(completed, backlogs, want):
    row = {"rate": 800.0, "sent": 24_000, "completed": completed,
           "backlog_before_insert": backlogs}
    assert sustained(row) is want


def test_knee_stops_at_the_first_rate_not_sustained():
    rows = [{"rate": r, "sustained": s}
            for r, s in ((720.0, True), (560.0, True), (880.0, False),
                         (1040.0, True))]
    assert knee(rows) == 720.0
    assert knee([{"rate": 560.0, "sustained": False}]) is None
