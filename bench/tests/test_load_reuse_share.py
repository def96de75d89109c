"""The reader of ``load_reuse_share``: by hand on a made-up window, and in a
small traced run of each declared cell on the CPU."""
import json

import pytest
from conftest import ROOT, small_run

from bench import harness


def span(name, t0, t1, **args):
    return {"name": name, "t0": t0, "t1": t1, "main": False, "args": args}


# a window [10, 20) s: the first load starts before it, the last after it
WINDOW = {
    "t0": 10.0, "t1": 20.0,
    "spans": [
        span("plan/load", 9.5, 10.5, rows=8, bytes=64, reused=0),
        span("plan/load", 10.0, 10.4, rows=8, bytes=64, reused=0),
        span("plan/load", 12.0, 12.5, rows=8, bytes=64, reused=1),
        span("plan/load", 15.0, 16.0, rows=8, bytes=64, reused=1),
        span("plan/load", 16.0, 17.0, rows=8, bytes=64, reused=1),
        span("plan/load", 20.0, 21.0, rows=8, bytes=64, reused=0),
        span("plan/build", 12.0, 13.0, cpu_s=1.0, reused=0),  # not a load
    ],
}


def read(run):
    return harness.load_metric("load_reuse_share").read(run)


def test_reader_on_a_made_up_window():
    assert read(WINDOW) == pytest.approx(100.0 * 3 / 4)


def test_reader_finds_nothing():
    """No load span, or loads without the counter (a program without the
    pool): the reader gives None and the result line leaves the metric out."""
    assert read(dict(WINDOW, spans=[])) is None
    bare = [dict(s, args={k: v for k, v in s["args"].items() if k != "reused"})
            for s in WINDOW["spans"]]
    assert read(dict(WINDOW, spans=bare)) is None


BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_traced_small_run_reuses_the_blocks(name):
    """The warm-up epoch fills the pool: the window's loads reuse blocks, in
    each cell that declares the metric."""
    result = small_run(name, 2**31 + 29, True)
    assert result["correct"], result["checks"]
    if "load_reuse_share" in {e["name"] for e in harness.declared(name, BENCH)[1]}:
        assert result["metrics"]["load_reuse_share"]["value"] > 50
