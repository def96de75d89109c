"""The plain reference against the program's step, and its control.

The harness's check compares the program's first steps with the reference
(``test_harness``: at small shapes on the CPU they agree to rounding); here,
the reference's own check of its blocks, and its bfloat16 control.
"""
import json

import numpy as np
import pytest
from conftest import ROOT, small_cell

from bench import correct, graphgen, reference

CELLS = [w["name"] for w in json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]


def sampled_blocks(cell, cfg, graph, seed, n=3):
    """``n`` blocks of distinct targets, sampled by the program's sampler."""
    from repro.graph.csr import CSRGraph
    from repro.graph.sampling import NeighborSampler

    sampler = NeighborSampler(CSRGraph(graph.indptr, graph.indices), graph.train_ids,
                              cell["fanouts"], cell["batch_size"], seed=seed)
    out = []
    for i, targets in enumerate(sampler.epoch_targets(0)[:n]):
        s = sampler.sample_batch(targets, 0, i)
        out.append({"frontiers": s.frontiers,
                    "layers": [(layer.src, layer.dst) for layer in s.layers]})
    return out


@pytest.mark.parametrize("name", CELLS)
def test_reference_checks_its_blocks(name):
    cell, cfg = small_cell(name)
    graph = graphgen.generate(cfg, 3)
    block = sampled_blocks(cell, cfg, graph, 3, n=1)[0]
    reference.block_arrays(block, graph, cell["fanouts"], cell["batch_size"])
    src, dst = block["layers"][0]
    bad = dict(block, layers=[(src[::-1].copy(), dst)] + block["layers"][1:])
    with pytest.raises(reference.BlockError):
        reference.block_arrays(bad, graph, cell["fanouts"], cell["batch_size"])


@pytest.mark.parametrize("name", CELLS)
def test_control_runs_in_bfloat16(name):
    """The control (the reference computed in bfloat16, put in the program's
    place) at the published widths on a small graph fails the cell's check
    against the reference at the configuration's precision: it trains, and
    departs by bfloat16 rounding (on the chip: PERF.md)."""
    cell, cfg = small_cell(name, wide=True)
    seed = 101
    graph = graphgen.generate(cfg, seed)
    blocks = sampled_blocks(cell, cfg, graph, seed)
    params0 = reference.init_params(cfg, seed)
    args = (cfg, params0, blocks, graph, cell["fanouts"], cell["batch_size"])
    precision = cfg["matmul_precision"]
    ref = reference.run_steps(*args, precision=precision)
    control = reference.run_steps(*args, dtype="bfloat16", precision=precision)
    nums = correct.numbers(control, ref, params0)
    assert np.isfinite(list(nums.values())).all()
    assert not correct.judge(nums, cell["correct_limits"]), nums


def test_run_steps_pinned():
    """The reference's three steps on a small seeded CPU run of
    ``sage-orkut.single`` give the losses that the reference gave before its
    layer moved to ``bench/models/sage.py``, bit for bit."""
    cell, cfg = small_cell("sage-orkut.single")
    seed = 2**31 + 77
    graph = graphgen.generate(cfg, 5)
    blocks = sampled_blocks(cell, cfg, graph, seed)
    params0 = reference.init_params(cfg, seed)
    args = (cfg, params0, blocks, graph, cell["fanouts"], cell["batch_size"])
    assert reference.run_steps(*args, precision="default")["losses"] == [
        4.101616859436035, 3.5404157638549805, 3.4140634536743164]
    assert reference.run_steps(*args, precision="default", dtype="bfloat16")["losses"] == [
        4.096427917480469, 3.546271800994873, 3.413743257522583]
