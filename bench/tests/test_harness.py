"""Whole runs of each declared cell at small shapes on the CPU: the run's
set-up, window and check, without the look for a chip."""
import json
import os
import subprocess
import sys

import pytest
from conftest import ROOT, small_run

from bench import harness

CELLS = [w["name"] for w in json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]


def test_refuses_a_platform_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


@pytest.mark.parametrize("trace", [0, 1], ids=["trace0", "trace1"])
@pytest.mark.parametrize("name", CELLS)
def test_small_run_is_correct(name, trace):
    result = small_run(name, 2**31 + 17, bool(trace))
    assert result["correct"], result["checks"]
    # the CPU runs float32 matmuls exactly: the program's step and the plain
    # reference agree to rounding on the loss and the first gradient
    assert result["checks"]["loss_gap"]["value"] < 1e-5
    assert result["checks"]["grad_norm_gap"]["value"] < 1e-5
    assert result["attempted"] > 0 and result["failed"] == 0
    assert list(result)[-1] == "checks"
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    e2e, layer = harness.declared(name, bench)
    if trace:
        assert {"busy_s", "window_s"} <= set(result["device"])
        # every metric the cell declares from spans or the host's clock is
        # read; the CPU runs no TPU op, so the device-trace readers may not be
        assert set(result["metrics"]) >= {
            m["name"] for m in layer if m["source"] in ("program_span", "host_clock")}
        assert set(result["metrics"]) <= {m["name"] for m in layer}
        assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        assert set(result["metrics"]) == {m["name"] for m in e2e}
        assert all(m["value"] > 0 for m in result["metrics"].values())
