"""The readers of the span counters: by hand on made-up windows, and in a
small traced run of each declared cell on the CPU."""
import json

import pytest
from conftest import ROOT, small_run

from bench import harness

COUNTER_METRICS = ("plan_cpu_share", "load_gbps", "stage_gbps", "pad_row_share")


def span(name, t0, t1, main=False, **args):
    return {"name": name, "t0": t0, "t1": t1, "main": main, "args": args}


# a window [10, 20) s: the first span of each kind starts before it
WINDOW = {
    "t0": 10.0, "t1": 20.0,
    "spans": [
        span("plan/build", 9.0, 11.0, cpu_s=2.0),
        span("plan/build", 11.0, 13.0, cpu_s=1.5),
        span("plan/build", 12.0, 16.0, cpu_s=2.5),
        span("plan/load", 9.5, 10.5, bytes=9e9),
        span("plan/load", 12.0, 12.5, bytes=2e9),
        span("plan/load", 15.0, 16.0, bytes=1e9),
        span("step/put", 9.9, 10.1, main=True, bytes=7e9),
        span("step/put", 11.0, 11.1, main=True, bytes=1e9),
        span("step/put", 12.0, 12.3, main=True, bytes=2e9),
        span("step/put", 12.0, 12.3, bytes=9e9),  # not the training loop's
        span("plan/repad", 9.0, 9.1, rows=1, rows_padded=100),
        span("plan/repad", 11.0, 11.1, rows=60, rows_padded=64),
        span("plan/repad", 13.0, 13.1, rows=100, rows_padded=128),
        span("step/wait", 10.0, 11.0, main=True),
    ],
}
EXPECTED = {
    "plan_cpu_share": 100.0 * 4.0 / 6.0,
    "load_gbps": 3.0 / 1.5,
    "stage_gbps": 3.0 / 0.4,
    "pad_row_share": 100.0 * 32 / 192,
}


@pytest.mark.parametrize("name", COUNTER_METRICS)
def test_reader_on_a_made_up_window(name):
    assert harness.load_metric(name).read(WINDOW) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", COUNTER_METRICS)
def test_reader_finds_nothing(name):
    """No span, or spans without the counters (a program that records none):
    the reader gives None and the result line leaves the metric out."""
    read = harness.load_metric(name).read
    assert read(dict(WINDOW, spans=[])) is None
    bare = [dict(s, args={}) for s in WINDOW["spans"]]
    assert read(dict(WINDOW, spans=bare)) is None


BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
IN_RANGE = {
    "plan_cpu_share": lambda v: 0 < v <= 100.5,
    "load_gbps": lambda v: v > 0,
    "stage_gbps": lambda v: v > 0,
    "pad_row_share": lambda v: 0 <= v < 100,
}


@pytest.mark.parametrize("name", CELLS)
def test_traced_small_run_reports_the_counters(name):
    """Each counter metric that the cell declares is read, in its range."""
    result = small_run(name, 2**31 + 23, True)
    assert result["correct"], result["checks"]
    m = {k: v["value"] for k, v in result["metrics"].items()}
    declared = {e["name"] for e in harness.declared(name, BENCH)[1]} & set(COUNTER_METRICS)
    assert declared <= set(m)
    for k in declared:
        assert IN_RANGE[k](m[k]), (k, m[k])
