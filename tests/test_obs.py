"""Unified tracing + metrics (repro.obs): recorder semantics, Chrome-trace
export schema, stall-attribution report, HWM-growth surfacing, and the
trainer integration contract — observation never perturbs the numerics."""
import json
import logging
import threading

import pytest

from repro.graph.datasets import make_dataset
from repro.models.gnn import GNNSpec
from repro.obs import NULL_OBS, Obs, Tracer, note_hwm_growth
from repro.obs.metrics import MetricsRegistry, percentile
from repro.obs.report import (
    classify_step,
    load_trace,
    summarize,
    validate_trace,
)
from repro.train.trainer import TrainConfig, Trainer


# --------------------------------------------------------------------- #
# metrics registry
# --------------------------------------------------------------------- #
def test_percentile_nearest_rank():
    vals = sorted(float(v) for v in range(1, 11))
    assert percentile(vals, 0) == 1.0
    assert percentile(vals, 50) == 5.0  # nearest rank on 10 items
    assert percentile(vals, 100) == 10.0
    assert percentile([], 50) == 0.0


def test_registry_kinds_and_summaries():
    reg = MetricsRegistry()
    reg.count("hits")
    reg.count("hits", 4)
    reg.gauge("occupancy", 3.5)
    for v in (1.0, 2.0, 3.0, 4.0):
        reg.observe("lat", v)
    snap = reg.snapshot()
    assert snap["hits"] == 5
    assert snap["occupancy"] == 3.5
    assert snap["lat"]["count"] == 4
    assert snap["lat"]["mean"] == 2.5
    assert snap["lat"]["max"] == 4.0


def test_registry_kind_conflict_raises():
    reg = MetricsRegistry()
    reg.count("x")
    with pytest.raises(TypeError, match="Counter"):
        reg.observe("x", 1.0)


def test_absorb_takes_numeric_leaves_only():
    reg = MetricsRegistry()
    reg.absorb(
        {"delivered": 7, "rate": 0.5, "name": "q", "flag": True, "sub": {}},
        prefix="src/",
    )
    snap = reg.snapshot()
    assert snap == {"src/delivered": 7.0, "src/rate": 0.5}


# --------------------------------------------------------------------- #
# tracer + span semantics
# --------------------------------------------------------------------- #
def test_span_times_without_tracer():
    with NULL_OBS.span("x") as sp:
        pass
    assert sp.duration >= 0.0
    assert NULL_OBS.tracer is None and NULL_OBS.metrics is None


def test_null_obs_calls_are_noops():
    NULL_OBS.count("c")
    NULL_OBS.observe("h", 1.0)
    NULL_OBS.instant("i")
    NULL_OBS.flow_start(("p", 0, 0))
    NULL_OBS.flow_end(("p", 0, 0))
    with pytest.raises(ValueError, match="disabled"):
        NULL_OBS.write("/dev/null")


def test_tracer_records_nested_spans_and_flows():
    tr = Tracer()
    with tr.span("outer", {"epoch": 0}):
        tr.flow_start(("plan", 0, 0))
        with tr.span("inner"):
            pass
    with tr.span("step"):
        tr.flow_end(("plan", 0, 0))
    tr.flow_start(("plan", 0, 99))  # never finished -> unresolved
    chrome = tr.to_chrome({"m": 1})

    xs = [e for e in chrome["traceEvents"] if e["ph"] == "X"]
    names = [e["name"] for e in xs]
    # rings append at span *exit*: inner closes before outer
    assert names == ["inner", "outer", "step"]
    outer = next(e for e in xs if e["name"] == "outer")
    assert outer["args"] == {"epoch": 0}
    flows = [e for e in chrome["traceEvents"] if e["ph"] in ("s", "f")]
    assert [e["ph"] for e in flows] == ["s", "f"]
    assert chrome["otherData"]["unresolved_flows"] == 1
    assert chrome["otherData"]["unclosed_spans"] == 0
    assert chrome["otherData"]["metrics"] == {"m": 1}
    # the dangling flow is the one (and only) violation the validator sees
    assert validate_trace(chrome) == [
        "1 flow id(s) with a missing endpoint"
    ]


def test_ring_overflow_drops_oldest_and_counts():
    tr = Tracer(ring_capacity=4)
    for i in range(10):
        with tr.span(f"s{i}"):
            pass
    assert tr.dropped_events() == 6
    chrome = tr.to_chrome()
    names = [e["name"] for e in chrome["traceEvents"] if e["ph"] == "X"]
    assert names == ["s6", "s7", "s8", "s9"]  # oldest evicted
    assert chrome["otherData"]["dropped_events"] == 6
    assert any("dropped" in err for err in validate_trace(chrome))


def test_threads_get_their_own_lanes():
    tr = Tracer()

    def worker():
        with tr.span("produced"):
            pass

    t = threading.Thread(target=worker, name="producer-0")
    t.start()
    t.join()
    with tr.span("consumed"):
        pass
    chrome = tr.to_chrome()
    tids = {
        e["name"]: e["tid"] for e in chrome["traceEvents"] if e["ph"] == "X"
    }
    assert tids["produced"] != tids["consumed"]
    lanes = {
        e["args"]["name"]
        for e in chrome["traceEvents"]
        if e["ph"] == "M" and e["name"] == "thread_name"
    }
    assert "producer-0" in lanes


def test_unclosed_span_flagged_at_export():
    tr = Tracer()
    sp = tr.span("open")
    sp.__enter__()
    chrome = tr.to_chrome()
    assert chrome["otherData"]["unclosed_spans"] == 1
    assert any("unclosed" in err for err in validate_trace(chrome))


def test_obs_write_and_load_roundtrip(tmp_path):
    obs = Obs(enabled=True)
    with obs.span("a"):
        pass
    obs.count("n", 3)
    path = tmp_path / "trace.json"
    obs.write(path)
    trace = load_trace(path)
    assert validate_trace(trace) == []
    assert trace["otherData"]["metrics"]["n"] == 3


# --------------------------------------------------------------------- #
# validation + report
# --------------------------------------------------------------------- #
def _ev(name, ts, dur=None, ph="X", **kw):
    ev = {"ph": ph, "name": name, "ts": ts, "pid": 0, "tid": 1, **kw}
    if dur is not None:
        ev["dur"] = dur
    return ev


def test_validate_catches_structural_breakage():
    bad = {
        "traceEvents": [
            {"ph": "Z", "name": "?", "ts": 0, "pid": 0, "tid": 1},
            _ev("no-dur", 10.0),
            _ev("negative", -5.0, 1.0),
            _ev("later", 100.0, 10.0),
            _ev("regressed", 50.0, 10.0),  # record time goes backwards
            _ev("flow", 1.0, ph="s", id=7),  # never finished
        ],
        "otherData": {},
    }
    errors = validate_trace(bad)
    assert any("unknown ph" in e for e in errors)
    assert any("missing/negative dur" in e for e in errors)
    assert any("negative ts" in e for e in errors)
    assert any("regresses" in e for e in errors)
    assert any("flow 7" in e and "unresolved" in e for e in errors)


def test_classify_step_picks_largest_component():
    assert classify_step({"wait_s": 0.5, "stage_s": 0.1}) == "producer-bound"
    assert classify_step({"stage_s": 0.9, "device_s": 0.2}) == "staging-bound"
    assert classify_step({"device_s": 1.0}) == "device-bound"


def test_summarize_stages_and_stalls():
    trace = {
        "traceEvents": [
            _ev("plan/build", 0.0, 1000.0),
            _ev("plan/build", 0.0, 3000.0),
            _ev("step", 0.0, 500.0,
                args={"wait_s": 0.9, "stage_s": 0.1, "device_s": 0.0}),
            _ev("step", 600.0, 500.0,
                args={"wait_s": 0.0, "stage_s": 0.1, "device_s": 0.8}),
        ],
        "otherData": {"metrics": {"sig/hit": 5}},
    }
    s = summarize(trace)
    assert s["steps"] == 2
    assert s["stages"]["plan/build"]["count"] == 2
    assert s["stages"]["plan/build"]["mean_ms"] == 2.0
    assert s["stall_classes"] == {
        "producer-bound": 1, "staging-bound": 0, "device-bound": 1,
    }
    assert s["metrics"] == {"sig/hit": 5}


def test_cli_validate_and_report(tmp_path, capsys):
    from repro.obs.__main__ import main

    obs = Obs(enabled=True)
    with obs.span("step", {"wait_s": 1.0, "stage_s": 0.0, "device_s": 0.0}):
        pass
    path = tmp_path / "t.json"
    obs.write(path)
    assert main(["validate", str(path)]) == 0
    assert "schema valid" in capsys.readouterr().out
    assert main(["report", str(path)]) == 0
    out = capsys.readouterr().out
    assert "producer-bound" in out and "stall attribution" in out

    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"traceEvents": [{"ph": "Z"}]}))
    assert main(["validate", str(bad)]) == 1


def test_load_trace_accepts_jsonl(tmp_path):
    path = tmp_path / "t.jsonl"
    path.write_text(
        "\n".join(json.dumps(_ev(f"s{i}", i * 10.0, 1.0)) for i in range(3))
    )
    trace = load_trace(path)
    assert len(trace["traceEvents"]) == 3
    assert validate_trace(trace) == []


# --------------------------------------------------------------------- #
# HWM growth surfacing (satellite: silent growth now warns)
# --------------------------------------------------------------------- #
def test_note_hwm_growth_classifies_and_warns(caplog):
    obs = Obs(enabled=True)
    before = {"N0": 32, "E1": 16}
    after = {"N0": 64, "E1": 16, "CM": 8}  # one grown, one flat, one new
    with caplog.at_level(logging.WARNING, logger="repro.obs"):
        grown = note_hwm_growth(obs, before, after, "epoch0/batch3")
    assert grown == 1
    warnings = [r for r in caplog.records if "high-water mark" in r.message]
    assert len(warnings) == 1
    assert "N0" in warnings[0].message
    assert "epoch0/batch3" in warnings[0].message
    assert obs.metrics.snapshot()["hwm/growth"] == 1
    names = [
        e["name"]
        for e in obs.tracer.to_chrome()["traceEvents"]
        if e["ph"] == "i"
    ]
    assert names.count("hwm/grow") == 1
    assert names.count("hwm/init") == 1  # first-seen marks are silent events


def test_note_hwm_growth_steady_state_is_silent(caplog):
    hwm = {"N0": 64}
    with caplog.at_level(logging.WARNING, logger="repro.obs"):
        assert note_hwm_growth(NULL_OBS, dict(hwm), hwm, "steady") == 0
    assert not caplog.records


# --------------------------------------------------------------------- #
# trainer integration: observation never perturbs
# --------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def ds():
    return make_dataset("tiny")


def _spec(ds):
    return GNNSpec(
        model="sage", in_dim=ds.spec.feat_dim, hidden_dim=16,
        out_dim=ds.spec.num_classes, num_layers=2, num_heads=4,
    )


def _run(ds, source, obs_path=None, epochs=2, iters=3):
    cfg = TrainConfig(
        mode="split", num_devices=4, fanouts=(4, 4), batch_size=32,
        presample_epochs=2, plan_source=source, pipeline_depth=2,
        plan_workers=2, seed=7,
        obs_trace=obs_path is not None,
        obs_path=str(obs_path) if obs_path else None,
    )
    tr = Trainer(ds, _spec(ds), cfg)
    traj = []
    for _ in range(epochs):
        st = tr.train_epoch(max_iters=iters)
        traj += [(i.loss, i.accuracy) for i in st.iters]
    return tr, traj


@pytest.mark.parametrize("source", ["serial", "pipelined"])
def test_tracing_is_observation_only(ds, tmp_path, source):
    path = tmp_path / f"{source}.json"
    _, plain = _run(ds, source)
    tr, traced = _run(ds, source, obs_path=path)
    assert traced == plain  # bit-exact: spans never touch the math

    trace = load_trace(path)
    assert validate_trace(trace) == []
    s = summarize(trace)
    assert s["steps"] == len(traced)
    # every consumer step is classified
    assert sum(s["stall_classes"].values()) == s["steps"]
    # the producer pipeline stages all appear on the timeline
    for stage in ("plan/build", "plan/sample", "plan/split", "plan/load",
                  "plan/repad", "plan/queue_dwell", "step/wait",
                  "step/stage", "step/device"):
        assert stage in s["stages"], f"missing {stage} spans"
    # producer build spans flow-link to consumer steps: all resolved
    flows = [e for e in trace["traceEvents"] if e["ph"] in ("s", "f")]
    assert len(flows) == 2 * len(traced)
    # batch 0 establishes the marks: the init instants are on the timeline
    instants = [
        e["name"] for e in trace["traceEvents"] if e["ph"] == "i"
    ]
    assert "hwm/init" in instants
    snap = trace["otherData"]["metrics"]
    assert snap["sig/hit"] + snap["sig/miss"] == len(traced)


def test_trainer_hwm_warning_fires_in_warmup_only(ds, caplog):
    # the overlap schedule's edge-half marks (EL/LEB) grow past batch 0 on
    # this seed, so the warmup epoch deterministically exercises the
    # formerly silent growth event; pow2 bucketing keeps later epochs flat
    with caplog.at_level(logging.WARNING, logger="repro.obs"):
        cfg = TrainConfig(
            mode="split", num_devices=4, fanouts=(4, 4), batch_size=32,
            presample_epochs=2, plan_source="serial", seed=7, obs_trace=True,
            shuffle_overlap=True,
        )
        tr = Trainer(ds, _spec(ds), cfg)
        tr.train_epoch(max_iters=3)
        warmup = [r for r in caplog.records if "high-water mark" in r.message]
        caplog.clear()
        tr.train_epoch(max_iters=3)
        steady = [r for r in caplog.records if "high-water mark" in r.message]
    assert warmup, "warmup epoch should report HWM growth"
    assert not steady, "steady state must not grow marks (stable jit sigs)"


def test_epoch_stats_fields_survive_with_obs_off(ds):
    tr, _ = _run(ds, "serial", epochs=1)
    st = tr.train_epoch(max_iters=2)
    for it in st.iters:
        assert it.t_sample > 0.0
        assert it.t_split > 0.0
        assert it.t_load > 0.0
        assert it.t_compute > 0.0


# --------------------------------------------------------------------- #
# the profiler's clock, counters where the work happens, named scopes
# --------------------------------------------------------------------- #
def _dp_trainer(ds, obs_trace, spec=None):
    """A one-device dp trainer on pipelined plans (the benchmark cell's
    shape at the tiny size)."""
    cfg = TrainConfig(
        mode="dp", num_devices=1, fanouts=(4, 4), batch_size=32,
        plan_source="pipelined", pipeline_depth=2, plan_workers=2, seed=7,
        obs_trace=obs_trace,
    )
    return Trainer(ds, spec or _spec(ds), cfg)


def _ring_spans(tr, name):
    return [
        e for e in tr.obs.tracer.to_chrome()["traceEvents"]
        if e["ph"] == "X" and e["name"] == name
    ]


def test_profiler_trace_carries_the_spans(ds, tmp_path):
    import glob

    import jax
    from jax.profiler import ProfileData

    tr = _dp_trainer(ds, obs_trace=True)
    with jax.profiler.trace(str(tmp_path)):
        tr.train_epoch(max_iters=3)
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    names = ("plan/build", "plan/load", "step/wait", "step/put", "step/dispatch")
    host = {n: [] for n in names}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in host:
                        host[ev.name].append(ev.duration_ns / 1e3)
    for name in names:
        ring = [e["dur"] for e in _ring_spans(tr, name)]
        assert ring and len(host[name]) == len(ring), name
        # sorted pairing is the matching with the least largest gap: each
        # annotation is within 1 ms of its ring twin
        gaps = [abs(a - b) for a, b in zip(sorted(host[name]), sorted(ring))]
        assert max(gaps) < 1e3, (name, gaps)


def test_null_obs_makes_no_annotation_or_cpu_call(ds, monkeypatch):
    import time

    import jax

    def forbidden(*a, **k):
        raise AssertionError("called with obs off")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", forbidden)
    monkeypatch.setattr(jax.profiler, "StepTraceAnnotation", forbidden)
    monkeypatch.setattr(time, "thread_time", forbidden)
    tr = _dp_trainer(ds, obs_trace=False)
    assert tr.obs is NULL_OBS
    assert len(tr.train_epoch(max_iters=2).iters) == 2
    with NULL_OBS.span("x", cpu=True, step_num=3) as sp:
        pass
    assert sp.duration >= 0.0 and sp.attrs is None
    # the same patch stops a live tracer: the off path really skipped it
    with pytest.raises(AssertionError, match="obs off"):
        with Tracer().span("x", cpu=True):
            pass


def test_span_counters(ds, monkeypatch):
    import jax
    import numpy as np

    import repro.train.trainer as trainer_mod

    tr = _dp_trainer(ds, obs_trace=True)
    true_rows, staged = {}, []
    build, stage_batch = tr.producer.build, trainer_mod.stage_batch

    def keep_build(epoch, index, targets):
        pb = build(epoch, index, targets)
        true_rows[(epoch, index)] = int(pb.plan.node_mask[-1].sum())
        return pb

    def keep_stage(plan, feats, labels, *a, **k):
        out = stage_batch(plan, feats, labels, *a, **k)
        leaves = jax.tree_util.tree_leaves(out)
        staged.append((feats.shape, sum(x.nbytes for x in leaves), len(leaves)))
        return out

    monkeypatch.setattr(tr.producer, "build", keep_build)
    monkeypatch.setattr(trainer_mod, "stage_batch", keep_stage)
    tr.train_epoch(max_iters=3)

    F = ds.features.shape[1]
    assert ds.features.dtype == np.float32
    builds = _ring_spans(tr, "plan/build")
    loads = _ring_spans(tr, "plan/load")
    assert len(builds) == len(loads) == len(true_rows) > 0
    for b in builds:
        a = b["args"]
        assert 0 < a["cpu_s"] <= b["dur"] / 1e6 + 5e-3
        assert a["worker"] in (0, 1)
        # the load span nested in this build, on the same thread
        (load,) = [
            e for e in loads if e["tid"] == b["tid"]
            and b["ts"] <= e["ts"] and e["ts"] + e["dur"] <= b["ts"] + b["dur"]
        ]
        assert load["args"]["rows"] == true_rows[(a["epoch"], a["batch"])]
        assert load["args"]["bytes"] == load["args"]["rows"] * F * 4
    puts = _ring_spans(tr, "step/put")
    assert [(p["args"]["bytes"], p["args"]["arrays"]) for p in puts] == [
        (nbytes, n) for _, nbytes, n in staged
    ]
    repads = _ring_spans(tr, "plan/repad")
    assert len(repads) == len(staged)
    for r, (shape, _, _) in zip(repads, staged):
        assert shape[0] == 1  # one device: the staged height is the block's
        assert 0 < r["args"]["rows"] <= r["args"]["rows_padded"] == shape[1]


@pytest.mark.parametrize(
    "model,backend", [("sage", "jnp"), ("sage", "pallas"), ("gat", "jnp")]
)
def test_step_hlo_carries_named_scopes(ds, model, backend):
    import re

    from repro.train.plan_io import stage_batch

    spec = GNNSpec(
        model=model, in_dim=ds.spec.feat_dim, hidden_dim=16,
        out_dim=ds.spec.num_classes, num_layers=2, num_heads=4,
        agg_backend=backend,
    )
    tr = _dp_trainer(ds, obs_trace=False, spec=spec)
    src = tr.plan_source_for(0, max_iters=1)
    try:
        batch = next(iter(src))
    finally:
        src.close()
    lowered = tr._step_fn.lower(
        tr.params, tr.opt_state,
        *stage_batch(batch.plan, batch.feats, batch.labels),
    )
    op_names = re.findall(r'loc\("(jit\([^"]*)"', lowered.as_text(debug_info=True))
    assert any("gnn/layer0" in n for n in op_names)
    assert any(re.search(r"[/(]agg[/)]", n) for n in op_names)
    assert any(re.search(r"[/(]optimizer[/)]", n) for n in op_names)
    if model == "gat":  # the attention scores and the edge softmax
        assert any(re.search(r"[/(]attn_score[/)]", n) for n in op_names)
        assert any(re.search(r"[/(]softmax[/)]", n) for n in op_names)
