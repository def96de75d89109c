"""The reduction from a device trace to busy time, idle gaps and op time:
by hand on a made-up trace, and on a short trace recorded on a v5e chip."""
import json
from pathlib import Path

import pytest

from bench import harness, trace_reduce

MS = 1_000_000  # nanoseconds
# two devices' ops; window [0, 10 ms)
MADE_UP = {
    "ops": [
        [["a", 1 * MS, 2 * MS], ["b", 2 * MS, 2 * MS], ["a", 6 * MS, 1 * MS],
         ["c", 9 * MS, 3 * MS]],
        [["a", 0, 4 * MS]],
    ],
    "marks": {harness.WINDOW_MARK: [0, 10 * MS]},
}


def test_merged_clips_and_joins():
    assert trace_reduce.merged(MADE_UP["ops"][0], 0, 10 * MS) == [
        (1 * MS, 4 * MS), (6 * MS, 7 * MS), (9 * MS, 10 * MS)]


def test_busy_is_averaged_over_devices():
    # device 0: 3 + 1 + 1 ms busy; device 1: 4 ms
    assert trace_reduce.busy_seconds(MADE_UP, 0, 10 * MS) == pytest.approx(4.5e-3)


def test_idle_gaps_longest_first():
    assert trace_reduce.idle_gaps(MADE_UP, 0, 10 * MS) == [
        (4 * MS, 6 * MS), (7 * MS, 9 * MS), (0, 1 * MS)]


def test_op_seconds_by_name_and_pattern():
    got = trace_reduce.op_seconds(MADE_UP, 0, 10 * MS)
    assert got == pytest.approx({"a": 3.5e-3, "b": 1e-3, "c": 0.5e-3})
    assert trace_reduce.op_seconds(MADE_UP, 0, 10 * MS, ["^c$"]) == pytest.approx(
        {"c": 0.5e-3})


RECORDED = sorted((Path(__file__).parent / "data").glob("*.json"))


@pytest.mark.parametrize("path", RECORDED, ids=lambda p: p.stem)
def test_recorded_trace(path):
    """A slice of a window traced on one v5e chip: the reduction's sums hold
    together."""
    trace = json.loads(path.read_text())
    ops = trace["ops"][0]
    t0 = min(s for _, s, _ in ops)
    t1 = max(s + d for _, s, d in ops)
    busy = trace_reduce.busy_seconds(trace, t0, t1)
    gaps = trace_reduce.idle_gaps(trace, t0, t1)
    assert 0 < busy <= (t1 - t0) / 1e9
    assert busy + sum(b - a for a, b in gaps) / 1e9 == pytest.approx((t1 - t0) / 1e9)
    total = sum(trace_reduce.op_seconds(trace, t0, t1).values())
    assert total >= busy * (1 - 1e-9)
