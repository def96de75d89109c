"""Readings that the limits of a cell's check are set from (run on the chip).

    python bench/calibrate.py --workload <cell> --seeds 1 2 3 ... \
        [--control-seeds 1 2 3] [--fault-seeds 1 2 3]

For each seed, at the cell's own sizes and through the run's own set-up and
first steps (``harness``), it prints one JSON line with the compared
numbers (``bench/correct.py``) against the reference at the configuration's
matmul precision (the check's own), and under ``<name>@highest`` the same
against the reference at full float32, of:

* ``program``: the program's first three steps (the lower reading of each
  limit is the largest over sound seeds);
* ``control`` (``--control-seeds``): the reference computed in bfloat16, put
  in the program's place (an upper reading);
* ``half_batch`` (``--fault-seeds``): the program with a fault planted
  under its step, its first steps run again from the same weights on the
  same blocks: the loss taken over half of each batch's targets.

A state that the step leaves unchanged reads 1 on ``update_norm_gap`` by the
measure's definition and needs no run. The benchmark's own runs never run
this file.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def half_batch_loss(xent):
    """``xent`` over the first half of each batch's valid targets."""
    import jax.numpy as jnp

    def loss(logits, labels, mask):
        flat = mask.reshape(-1)
        keep = jnp.cumsum(flat) <= flat.sum() // 2
        return xent(logits, labels, (flat & keep).reshape(mask.shape))

    return loss


def rerun_with(tr, cfg, params0, patches: dict):
    """The first steps again, from ``params0``, with ``patches`` applied to
    the trainer module's globals for the retrace."""
    import jax
    import jax.numpy as jnp

    import repro.train.trainer as trainer_mod
    from bench import harness

    saved = {k: harness.program_attr(trainer_mod, k) for k in patches}
    try:
        for k, v in patches.items():
            setattr(trainer_mod, k, v)
        rebuild_step(tr)
        params = jax.tree_util.tree_map(jnp.asarray, params0)
        harness.set_program_attr(tr, "params", params)
        harness.set_program_attr(tr, "opt_state", tr.opt.init(params))
        harness.set_program_attr(tr, "_epoch", 0)
        harness.set_program_attr(tr, "global_step", 0)
        return harness.first_steps(tr, cfg, params0)
    finally:
        for k, v in saved.items():
            setattr(trainer_mod, k, v)


def rebuild_step(tr) -> None:
    """Trace the trainer's step anew, from the trainer module's globals."""
    from bench import harness

    step, cached = harness.program_attr(tr, "_build_step")()
    harness.set_program_attr(tr, "_step_fn", step)
    harness.set_program_attr(tr, "_cached_step_fn", cached)
    tr.recompiles.register("step", step)


def diff_norms(got: dict, ref: dict, params0) -> dict:
    """Candidate numbers beside the compared ones: per leaf, the norm of the
    difference over the larger of the reference leaf's norm and the median
    leaf's, worst leaf and median leaf, of the first gradient and of the
    change over the three steps; the median leaf's gap of change norms; the
    first step's loss gap."""
    import numpy as np

    from bench.correct import _leaves

    def per_leaf(a, b):
        w = np.array([np.linalg.norm(x) for x in b])
        d = np.array([np.linalg.norm(x - y) for x, y in zip(a, b)])
        r = d / np.maximum(w, np.median(w))
        return float(r.max()), float(np.median(r))

    p0 = _leaves(params0)
    g = per_leaf(_leaves(got["grad1"]), _leaves(ref["grad1"]))
    u = per_leaf([a - c for a, c in zip(_leaves(got["params"]), p0)],
                 [b - c for b, c in zip(_leaves(ref["params"]), p0)])
    first = abs(got["losses"][0] - ref["losses"][0]) / abs(ref["losses"][0])
    dn = np.array([np.linalg.norm(a - c) for a, c in zip(_leaves(got["params"]), p0)])
    rn = np.array([np.linalg.norm(b - c) for b, c in zip(_leaves(ref["params"]), p0)])
    gap_median = float(np.median(np.abs(dn - rn) / np.maximum(rn, np.median(rn))))
    return {"grad_diff_worst": g[0], "grad_diff_median": g[1],
            "update_diff_worst": u[0], "update_diff_median": u[1],
            "update_gap_median": gap_median, "first_loss_gap": first}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--fault-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)

    from bench import harness

    cell, cfg = harness.load_cell(args.workload)
    harness.require_devices(cell["chips"])
    sys.path.insert(0, str(harness.ROOT / "src"))
    import repro.train.trainer as trainer_mod
    from bench import correct, graphgen, reference
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    t = time.perf_counter()
    graph = graphgen.generate(cfg, int(cfg["graph_seed"]))
    tr = harness.make_trainer(cfg, cell, graph, trace=False)
    print(f"set-up {time.perf_counter() - t!r} s", flush=True)
    precision = cfg["matmul_precision"]
    for seed in args.seeds:
        params0, recorder = harness.start(tr, cfg, cell, seed)
        prog = harness.first_steps(tr, cfg, params0)
        blocks = [recorder.blocks[i] for i in range(harness.CHECK_STEPS)]
        row = {"workload": args.workload, "seed": seed}
        runs = {"program": prog}
        if seed in args.fault_seeds:
            runs["half_batch"] = rerun_with(
                tr, cfg, params0,
                {"masked_softmax_xent": half_batch_loss(trainer_mod.masked_softmax_xent)})
            rebuild_step(tr)
        ref_args = (cfg, params0, blocks, graph, cell["fanouts"], int(cell["batch_size"]))
        refs = {"": reference.run_steps(*ref_args, precision=precision),
                "@highest": reference.run_steps(*ref_args, precision="highest")}
        if seed in args.control_seeds:
            runs["control"] = reference.run_steps(*ref_args, dtype="bfloat16",
                                                  precision=precision)
        for name, got in runs.items():
            for tag, ref in refs.items():
                if tag and name not in ("program", "control"):
                    continue
                row[name + tag] = dict(correct.numbers(got, ref, params0),
                                       **diff_norms(got, ref, params0))
        row["losses"] = {"reference": refs[""]["losses"], "program": prog["losses"]}
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
