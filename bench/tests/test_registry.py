"""Every declared entry of BENCHMARK.json is found by name from its own file,
and the file keeps to the benchmark's format."""
import json
import re

import pytest
from conftest import ROOT

from bench import counts, harness, reference

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda e: e["name"])
def test_config_found_by_name(entry):
    cfg = json.loads((ROOT / entry["file"]).read_text())
    assert entry["file"] == f"bench/configs/{entry['name']}.json"
    assert cfg["name"] == entry["name"]
    assert cfg["reduced"] == entry["reduced"]
    for key in entry["reduced"]:
        assert NAME.match(key) and key in cfg
        assert not key.endswith(("_dim", "_rank")) and "hidden" not in key


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda e: e["name"])
def test_model_found_by_name(entry):
    """The configuration's model brings its weights, plain reference layer
    and work count in ``bench/models/<model>.py``."""
    model = json.loads((ROOT / entry["file"]).read_text())["model"]
    assert (ROOT / "bench" / "models" / f"{model}.py").is_file()
    mod = reference.model({"model": model})
    for name in ("init_params", "layer", "step_flops"):
        assert callable(getattr(mod, name)), name


def test_unknown_model_names_its_file():
    cfg = {"model": "no_such_model", "num_layers": 1, "feat_dim": 2, "hidden_dim": 2,
           "num_classes": 2}
    for call in (lambda: reference.init_params(cfg, 1),
                 lambda: counts.step_flops(cfg, [{"n_dst": 1, "n_src": 1, "edges": 1}])):
        with pytest.raises(ValueError, match="bench/models/no_such_model.py"):
            call()
    with pytest.raises(ValueError, match="bench/metrics/no_such_metric.py"):
        harness.load_metric("no_such_metric")


@pytest.mark.parametrize("entry", BENCH["workloads"], ids=lambda e: e["name"])
def test_workload_found_by_name(entry):
    cell, cfg = harness.load_cell(entry["name"])
    for key in ("config", "traffic", "chips", "why"):
        assert cell[key] == entry[key], key
    assert cfg["name"] == entry["config"]
    assert entry["chips"] in (1, 4) and len(entry["why"]) <= 200
    assert set(cell["correct_limits"]) == {"loss_gap", "grad_norm_gap", "update_norm_gap"}
    e2e, layer = harness.declared(entry["name"], BENCH)
    assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2 and layer


@pytest.mark.parametrize("entry", BENCH["per_layer"], ids=lambda e: e["name"])
def test_metric_found_by_name(entry):
    assert callable(harness.load_metric(entry["name"]).read)
    assert entry["moves"] in {m["name"] for m in BENCH["end_to_end"]}
    assert set(entry["workloads"]) <= {w["name"] for w in BENCH["workloads"]}


def test_names_units_and_bounds():
    names = [e["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for e in BENCH[k]]
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    layers = {m["layer"] for m in BENCH["per_layer"]}
    assert all(len(x) <= 200 and "\n" not in x for x in layers)


def test_peaks_known_and_unknown_kind():
    assert harness.device_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        harness.device_peaks("TPU v99")
