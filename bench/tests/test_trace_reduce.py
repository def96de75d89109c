"""The reduction from a device trace to busy time, idle gaps, op time and
the time of named scopes: by hand on a made-up trace and HLO text, on a
short trace recorded on a v5e chip, and on a small traced run on the CPU."""
import json
from functools import partial
from pathlib import Path

import pytest
from conftest import small_run

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


# --------------------------------------------------------------------------- #
# named scopes: the trace's ops joined with the step's optimized HLO
# --------------------------------------------------------------------------- #
HLO = """HloModule jit_step, is_scheduled=true

%fused_computation.1 (param_0: f32[8,4], param_1: s32[8]) -> f32[8,4] {
  %param_0 = f32[8,4]{1,0} parameter(0)
  %param_1 = s32[8]{0} parameter(1)
  %gather.1 = f32[8,4]{1,0} gather(f32[8,4]{1,0} %param_0, s32[8]{0} %param_1), metadata={op_name="jit(step)/jvp(gnn/layer0)/vmap(agg)/gather"}
  ROOT %multiply.1 = f32[8,4]{1,0} multiply(%gather.1, %gather.1), metadata={op_name="jit(step)/transpose(jvp(gnn/layer0))/vmap(agg)/mul"}
}

%fused_computation.2 (param_0.1: f32[8,4]) -> f32[8,4] {
  %param_0.1 = f32[8,4]{1,0} parameter(0)
  %fusion.3 = f32[8,4]{1,0} fusion(%param_0.1, %param_0.1), kind=kLoop, calls=%fused_computation.1
  ROOT %dot.1 = f32[8,4]{1,0} dot(%fusion.3, %param_0.1), metadata={op_name="jit(step)/jvp(gnn/layer0)/dot_general"}
}

%region_0 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0), metadata={op_name="scatter-add"}
  %b = f32[] parameter(1), metadata={op_name="scatter-add"}
  ROOT %add.9 = f32[] add(%a, %b), metadata={op_name="scatter-add"}
}

ENTRY %main.1 (p0: f32[8,4], p1: s32[8]) -> f32[8,4] {
  %p0 = f32[8,4]{1,0} parameter(0)
  %p1 = s32[8]{0} parameter(1)
  %fusion.1 = f32[8,4]{1,0:T(8,128)} fusion(f32[8,4]{1,0} %p0, s32[8]{0} %p1), kind=kCustom, calls=%fused_computation.1, backend_config={"a":[]}
  %fusion.2 = f32[8,4]{1,0} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(step)/jvp(gnn/layer0)/dot_general" stack_frame_id=3}
  %scatter.1 = f32[8,4]{1,0} scatter(%fusion.2, %p1, %p0), to_apply=%region_0, metadata={op_name="jit(step)/jvp(gnn/layer1)/agg/scatter-add"}
  %copy-start.1 = (f32[8,4]{1,0}, f32[8,4]{1,0}, u32[]) copy-start(%p0)
  ROOT %add.2 = f32[8,4]{1,0} add(%scatter.1, %p0), metadata={op_name="jit(step)/optimizer/add"}
}
"""
# one device, window [0, 10 ms); the op at 7 ms ran in a program not kept
SCOPED = {
    "ops": [[["fusion.1 fusion", 1 * MS, 2 * MS], ["fusion.2 fusion", 3 * MS, 1 * MS],
             ["copy-start.1 copy-start", 5 * MS, 1 * MS], ["add.2 add", 6 * MS, 1 * MS],
             ["fusion.1 fusion", 7 * MS, 1 * MS], ["scatter.1 scatter", 9 * MS, 3 * MS]]],
    "modules": [[["jit_step(11)", 0, 7 * MS], ["jit_other(12)", 7 * MS, 1 * MS],
                 ["jit_step(11)", 8 * MS, 5 * MS]]],
    "marks": {harness.WINDOW_MARK: [0, 10 * MS]},
}


def test_scope_components():
    assert trace_reduce.scope_components(
        "jit(step)/transpose(jvp(gnn/layer1))/vmap(agg)/mul") == ["step", "gnn", "layer1", "agg"]
    assert trace_reduce.scope_components("jit(step)/jvp(loss)/jit(take_along_axis)") == [
        "step", "loss", "take_along_axis"]
    assert trace_reduce.scope_components("scatter-add") == []


def test_hlo_scopes_follow_fusions():
    """A fusion's path is the deepest scope that all it runs shares; a
    reduction's body is not followed; an op with no metadata has none."""
    table = trace_reduce.hlo_scopes(HLO)
    assert trace_reduce.module_name(HLO) == "jit_step"
    assert table["fusion.1 fusion"] == "step/gnn/layer0/agg"
    assert table["fusion.2 fusion"] == "step/gnn/layer0"
    assert table["scatter.1 scatter"] == "step/gnn/layer1/agg"
    assert table["add.2 add"] == "step/optimizer"
    assert table["copy-start.1 copy-start"] is None


def test_scope_seconds_picks_the_scope_clipped_to_the_window():
    paths = trace_reduce.op_paths(SCOPED, [("jit_step", trace_reduce.hlo_scopes(HLO))])
    assert paths == [["step/gnn/layer0/agg", "step/gnn/layer0", None, "step/optimizer", None,
                      "step/gnn/layer1/agg"]]
    seconds = partial(trace_reduce.scope_seconds, SCOPED, paths, 0, 10 * MS)
    assert seconds("agg") == pytest.approx(3e-3)  # 2 ms, and 1 of the last op's 3
    assert seconds("layer0") == pytest.approx(3e-3)
    assert seconds("gnn") == pytest.approx(4e-3)
    assert seconds("optimizer") == pytest.approx(1e-3)
    assert seconds("mul") == 0.0  # a primitive is no scope
    assert seconds(None) == pytest.approx(2e-3)  # the copy, the program not kept


def test_an_instruction_with_two_scopes_in_two_modules_is_unscoped():
    other = trace_reduce.hlo_scopes(HLO.replace("vmap(agg)", "optimizer"))
    assert other["fusion.1 fusion"] == "step/gnn/layer0/optimizer"
    mine = trace_reduce.hlo_scopes(HLO)
    programs = [("jit_step", mine), ("jit_step", other)]
    # two kept programs of the module the trace names: the two paths of
    # fusion.1 leave it unscoped; fusion.2 and the scatter carry one path in
    # both
    assert trace_reduce.op_paths(SCOPED, programs) == [
        [None, "step/gnn/layer0", None, "step/optimizer", None, "step/gnn/layer1/agg"]]
    # the same where the trace names no module
    assert trace_reduce.op_paths(dict(SCOPED, modules=[[]]), programs) == \
        trace_reduce.op_paths(SCOPED, programs)
    # a program of another module name is not looked in where the trace
    # names the module; it is where the trace names none
    programs = [("jit_step", mine), ("jit_other", other)]
    assert trace_reduce.op_paths(SCOPED, programs)[0][0] == "step/gnn/layer0/agg"
    assert trace_reduce.op_paths(dict(SCOPED, modules=[[]]), programs)[0][0] is None


def test_agg_ms_per_step_reader():
    read = harness.load_metric("agg_ms_per_step").read
    paths = trace_reduce.op_paths(SCOPED, [("jit_step", trace_reduce.hlo_scopes(HLO))])
    run = {"steps": 2,
           "scope_seconds": partial(trace_reduce.scope_seconds, SCOPED, paths, 0, 10 * MS)}
    assert read(run) == pytest.approx(1.5)
    # no device op (the CPU), or none under the scope: nothing to read
    empty = {"ops": [[]], "modules": [[]], "marks": SCOPED["marks"]}
    assert read(dict(run, scope_seconds=partial(
        trace_reduce.scope_seconds, empty, [[]], 0, 10 * MS))) is None
    assert read(dict(run, scope_seconds=lambda scope: 0.0)) is None


def test_step_hlo_kept_on_the_cpu_carries_the_scopes(monkeypatch):
    """A small traced run of the cell keeps the text of the step its window
    ran, and its instructions map to the aggregation and the optimizer."""
    kept = {}
    context = harness.window_context

    def keep(*args):
        kept.update(context(*args))
        return kept

    monkeypatch.setattr(harness, "window_context", keep)
    result = small_run("sage-orkut.single", 2**31 + 31, True)
    assert result["correct"], result["checks"]
    assert len(kept["hlo"]) == 1  # the window replays one shape
    paths = set(trace_reduce.hlo_scopes(kept["hlo"][0]).values())
    assert any(p and "agg" in p.split("/") for p in paths)
    assert any(p and "optimizer" in p.split("/") for p in paths)
    assert kept["scope_seconds"]("agg") == 0.0  # the CPU runs no TPU op
