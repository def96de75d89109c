"""The program's GAT against its plain reference (``bench/models/gat.py``):
logits, loss, first gradient and one Adam step through the ``Trainer`` (dp,
P = 1, jnp aggregation) on seeded weights at small widths; planted variants
of the layer that the comparison catches; the work count by hand; and the
readers of the GAT scopes on a made-up trace."""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import small_cell

import repro.models.gnn.layers as layers_mod
from bench import counts, graphgen, harness, reference, trace_reduce
from bench.models import gat

SEED = 2**31 + 61
# feat 16 < hidden 32: the skip falls on the middle layer alone, as at the
# configuration's widths (512 < 1,024); 4 heads of 8, 4 classes averaged
SMALL_GAT = {"feat_dim": 16, "hidden_dim": 32, "num_heads": 4, "num_classes": 4}


def compare(seed: int = SEED) -> dict:
    """The program's first step and the reference's on the same block and
    weights, at ``highest`` matmul precision: the worst gaps of each kind."""
    from repro.core import sim_shuffle
    from repro.train.plan_io import plan_to_device

    cell, cfg = small_cell("gat-orkut.single")
    cfg = dict(cfg, **SMALL_GAT)
    graph = graphgen.generate(cfg, 7)
    with jax.default_matmul_precision("highest"):
        tr = harness.make_trainer(cfg, cell, graph, trace=False)
        params0, recorder = harness.start(tr, cfg, cell, seed)
        src = tr.plan_source_for(0, max_iters=1)
        try:
            batch = next(iter(src))
        finally:
            src.close()
        logits = np.asarray(layers_mod.gnn_forward(
            tr.spec, params0, jnp.asarray(batch.feats), plan_to_device(batch.plan),
            sim_shuffle))[0]
        ep = tr.train_epoch(max_iters=1)
        b1 = float(cfg["adam_b1"])
        prog = {"loss": ep.iters[0].loss,
                "grad": jax.device_get(tr.opt_state.slots["m"]),
                "params": jax.device_get(tr.params)}
        prog["grad"] = jax.tree_util.tree_map(lambda m: np.asarray(m) / (1 - b1), prog["grad"])
        block = recorder.blocks[0]
        ref = reference.run_steps(cfg, params0, [block], graph, cell["fanouts"],
                                  int(cell["batch_size"]), precision="highest")
        arrs, sizes = reference._pad_blocks(
            [reference.block_arrays(block, graph, cell["fanouts"], int(cell["batch_size"]))])
        blk = {k: [jnp.asarray(x) for x in arrs[0][k]] for k in ("src", "dst", "self")}
        ref_logits = np.asarray(reference.forward(
            jax.tree_util.tree_map(jnp.asarray, params0),
            jnp.asarray(graph.features[arrs[0]["inputs"]]), blk, sizes, jnp.float32,
            gat.layer))

    n = int(batch.plan.node_count[0][0])
    order = reference._find(batch.plan.front_ids[0][0, :n], np.asarray(block["frontiers"][0]))
    ref_logits = ref_logits[order]
    logits = logits[:n]
    gaps = {"logits": np.inf, "loss": abs(prog["loss"] - ref["losses"][0]) / abs(ref["losses"][0])}
    if logits.shape == ref_logits.shape:
        gaps["logits"] = float(np.abs(logits - ref_logits).max() / np.abs(ref_logits).max())
    g_prog, g_ref = jax.tree_util.tree_leaves(prog["grad"]), jax.tree_util.tree_leaves(ref["grad1"])
    gaps["grad"] = max(float(np.linalg.norm(a - b) / np.linalg.norm(b))
                       for a, b in zip(g_prog, g_ref))
    # Adam's first update is -lr g / (|g| + eps), about -lr sign(g): a
    # gradient element's rounding error (about 1e-6 of its leaf's largest)
    # moves it by lr eps err / g**2, nothing where |g| is 1e-4 of the leaf's
    # largest and 100 eps; below that its sign follows the rounding of g, so
    # those elements are left out (and must be few)
    lr, kept, total, worst = float(cfg["lr"]), 0, 0, 0.0
    for p1, r1, p0, g in zip(*(jax.tree_util.tree_leaves(t) for t in
                              (prog["params"], ref["params"], params0, ref["grad1"]))):
        g = np.abs(np.asarray(g))
        big = g >= max(1e-4 * g.max(), 100 * float(cfg["adam_eps"]))
        d = np.abs((np.asarray(p1) - np.asarray(p0)) - (np.asarray(r1) - np.asarray(p0)))
        worst = max(worst, float(d[big].max(initial=0.0)) / lr)
        kept, total = kept + int(big.sum()), total + big.size
    gaps["adam_step"] = worst
    gaps["adam_kept"] = kept / total
    return gaps


# float32 on the CPU, every matmul at full precision: the program and the
# reference differ only in the order of their sums (the program adds masked
# padding slots as zeros and sums in its plan's edge order), so the gaps are
# float32 rounding over sums of a few hundred terms (about 1e-7 each), with
# room of a hundred
TOLERANCE = {
    "logits": 1e-5,  # largest gap over the largest logit
    "loss": 1e-5,  # relative
    "grad": 1e-4,  # worst leaf's norm of the difference over its norm
    # largest gap of the first update, in units of lr: the parameters (up to
    # 0.4) hold it to their float32 spacing, 3e-8 = 3e-5 lr; room of thirty
    "adam_step": 1e-3,
}


def test_program_matches_reference():
    gaps = compare()
    for k, tol in TOLERANCE.items():
        assert gaps[k] <= tol, (k, gaps)
    assert gaps["adam_kept"] > 0.9, gaps


HEADS_OUT = layers_mod._gat_heads_out


def concatenated_output(layer_params, agg, h_self):
    """The output layer's heads concatenated, not averaged."""
    H, dh = layer_params["w"].shape[1:]
    if layer_params["b"].shape[-1] == H * dh:
        return HEADS_OUT(layer_params, agg, h_self)
    return agg + jnp.tile(layer_params["b"], H)


def no_skip(layer_params, agg, h_self):
    """The skip across the middle layer left out."""
    return HEADS_OUT(layer_params, agg, h_self[..., :0])


@pytest.mark.parametrize("variant", [concatenated_output, no_skip],
                         ids=lambda f: f.__name__)
def test_planted_variant_is_caught(variant, monkeypatch):
    monkeypatch.setattr(layers_mod, "_gat_heads_out", variant)
    gaps = compare()
    assert any(gaps[k] > tol for k, tol in TOLERANCE.items()), gaps


def test_gat_step_flops_by_hand():
    # two transitions: into 2 targets from 3 vertices over 4 edges, and into
    # those 3 from 5 vertices over 6 edges; 2 heads; feat = hidden = 4, so
    # the input layer has the skip, and the output layer averages 2 heads
    block = {"frontiers": [np.arange(2), np.arange(3), np.arange(5)],
             "layers": [(np.zeros(4), np.zeros(4)), (np.zeros(6), np.zeros(6))]}
    cfg = {"model": "gat", "num_layers": 2, "feat_dim": 4, "hidden_dim": 4,
           "num_heads": 2, "num_classes": 2}
    # input layer (4 -> 2 heads of 2, 5 sources, 3 destinations, 6 edges):
    # projection 2*5*4*4 = 160, scores 2*5*4 + 2*3*4 = 64, logits and
    # LeakyReLU 2*6*2 = 24, softmax 5*6*2 = 60, weighted sum 2*6*2*2 = 48,
    # skip 12, bias 12, ReLU 12: forward 392; backward 2*392 less the
    # projection's input gradient 160
    first = 392 + 2 * 392 - 160
    # output layer (4 -> 2 heads of 2 classes, 3 sources, 2 destinations, 4
    # edges): projection 2*3*4*4 = 96, scores 2*3*4 + 2*2*4 = 40, logits 16,
    # softmax 40, weighted sum 32, head mean 8, bias 4: forward 236
    last = 3 * 236
    loss = 3 * 5 * 2 * 2
    assert counts.step_flops(cfg, counts.block_sizes(block)) == first + last + loss == 1784


def test_head_dims_refuse_a_width_the_heads_do_not_split():
    with pytest.raises(ValueError, match="does not split"):
        gat.head_dims({"num_layers": 2, "feat_dim": 4, "hidden_dim": 6, "num_heads": 4,
                       "num_classes": 2})


MS = 1_000_000  # nanoseconds
HLO = """\
HloModule jit_step, entry_computation_layout={()->f32[]}

ENTRY %main.1 (p0: f32[8,4]) -> f32[8,4] {
  %p0 = f32[8,4]{1,0} parameter(0)
  %fusion.1 = f32[8,4]{1,0} fusion(%p0), kind=kLoop, calls=%c1, metadata={op_name="jit(step)/jvp(gnn/layer0)/attn_score/add"}
  %fusion.2 = f32[8,4]{1,0} fusion(%fusion.1), kind=kLoop, calls=%c2, metadata={op_name="jit(step)/transpose(jvp(gnn/layer1))/softmax/exp"}
  %fusion.3 = f32[8,4]{1,0} fusion(%fusion.2), kind=kLoop, calls=%c3, metadata={op_name="jit(step)/jvp(gnn/layer0)/agg/mul"}
  ROOT %add.2 = f32[8,4]{1,0} add(%fusion.3, %p0), metadata={op_name="jit(step)/optimizer/add"}
}
"""
# one device, window [0, 10 ms): attention scores 2 ms + 1 ms, softmax 1 ms
# and 1 ms of a 2 ms op that runs past the window's end
TRACE = {
    "ops": [[["fusion.1 fusion", 0, 2 * MS], ["fusion.2 fusion", 2 * MS, 1 * MS],
             ["fusion.3 fusion", 3 * MS, 4 * MS], ["fusion.1 fusion", 7 * MS, 1 * MS],
             ["fusion.2 fusion", 9 * MS, 2 * MS]]],
    "modules": [[["jit_step(1)", 0, 11 * MS]]],
    "marks": {harness.WINDOW_MARK: [0, 10 * MS]},
}


@pytest.mark.parametrize("name,expected", [("attn_score_ms_per_step", 1.5),
                                           ("softmax_ms_per_step", 1.0)])
def test_scope_reader_on_a_made_up_trace(name, expected):
    read = harness.load_metric(name).read
    paths = trace_reduce.op_paths(TRACE, [("jit_step", trace_reduce.hlo_scopes(HLO))])
    run = {"steps": 2,
           "scope_seconds": partial(trace_reduce.scope_seconds, TRACE, paths, 0, 10 * MS)}
    assert read(run) == pytest.approx(expected)
    # a program without the scope (the parent's, or SAGE's), or no device op
    # (the CPU): nothing to read, and the result line leaves the metric out
    sage = trace_reduce.hlo_scopes(HLO.replace("attn_score", "agg").replace("softmax", "agg"))
    assert read(dict(run, scope_seconds=partial(
        trace_reduce.scope_seconds, TRACE, trace_reduce.op_paths(TRACE, [("jit_step", sage)]),
        0, 10 * MS))) is None
    assert read(dict(run, scope_seconds=lambda scope: 0.0)) is None
