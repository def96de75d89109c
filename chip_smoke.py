"""Smoke run of the split-parallel GNN trainer on TPU chips.

    python chip_smoke.py [--seed N]             # one chip
    python chip_smoke.py --chips 4 [--seed N]   # four chips

Both build an Orkut-shaped graph from the seed: the ``orkut-s`` shape
(average degree 64, 512 features, 16 classes, 10% training vertices) at
32,768 nodes, so each full batch has 1,024 target vertices. The model is the
paper's 3-layer GraphSAGE (hidden 256, fan-out 15/15/15, batch 1,024), with
random weights drawn from the seed. One cut: the offline presampling pass
that weights the partitioner runs for one epoch instead of ten.

One chip runs ``Trainer`` through ``train_epoch`` (pipelined plan source):
one warm-up step and six steady steps in split mode with the fused Pallas
aggregation (the P = 4 splits run vmapped on the one chip), the same in
data-parallel mode with the jnp aggregation, then one fixed plan's split
forward with the Pallas kernels against the jnp aggregation.

``--chips 4`` runs only the multi-chip path: forward, gradient and one
optimizer update of the split SAGE step under ``jax.shard_map`` on a (1, 4)
split mesh of the four chips, against the same update in sim mode on one
chip.

The times printed are smoke timings of a few steps, not benchmark numbers.
The last line of standard output is a JSON object with the device; a failed
check exits non-zero before it is printed.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

NUM_NODES = 32768
SPLITS = 4
FANOUTS = (15, 15, 15)
BATCH = 1024
STEADY_EPOCHS = 2  # of len(train_ids) // BATCH full batches each

# TPU matmuls on f32 run at default precision: bf16 operands (2**-9 relative
# rounding) with f32 accumulation. The Pallas kernels and the jnp
# aggregation feed the layers' matmuls sums that differ in their last bits,
# the operand rounding can then differ, and three layers compound it: the
# logits agree to about a percent of their largest magnitude, not to f32
# epsilon.
PALLAS_VS_JNP_TOL = 2e-2
# shard_map and sim run the same arithmetic on the same plan; the shuffles
# move rows exactly. What differs is how XLA tiles the per-split matmuls and
# where the split-sum of the gradient is taken (a vmapped contraction vs a
# cross-chip psum), i.e. f32 accumulation order, which can flip a bf16
# operand rounding downstream.
SPMD_VS_SIM_TOL = 5e-3


def require_tpu(count: int):
    """The JAX devices, after checking there are ``count`` TPU chips."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU, but JAX runs on platform {platform!r}"
        )
    if count > 1 and len(devices) != count:
        raise SystemExit(
            f"chip_smoke: --chips {count} needs {count} TPU chips; JAX "
            f"found {len(devices)}"
        )
    return devices


def orkut_dataset(seed: int, num_nodes: int = NUM_NODES):
    from repro.graph.datasets import SYNTHETIC_DATASETS, make_dataset

    spec = dataclasses.replace(SYNTHETIC_DATASETS["orkut-s"], num_nodes=num_nodes)
    return make_dataset(spec, seed=seed)


def make_trainer(ds, mode: str, agg_backend: str, seed: int):
    from repro.models.gnn import GNNSpec
    from repro.train.trainer import TrainConfig, Trainer

    spec = GNNSpec(
        model="sage", in_dim=ds.spec.feat_dim, out_dim=ds.spec.num_classes,
        agg_backend=agg_backend,
    )
    cfg = TrainConfig(
        mode=mode, num_devices=SPLITS, fanouts=FANOUTS, batch_size=BATCH,
        plan_source="pipelined", presample_epochs=1, trace_recompiles=True,
        seed=seed,
    )
    return Trainer(ds, spec, cfg)


def rel_err(got, want) -> float:
    """Largest absolute difference over the largest reference magnitude."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


def train_phase(name: str, tr) -> list[float]:
    """A warm-up step, then ``STEADY_EPOCHS`` epochs of full batches."""
    full = len(tr.ds.train_ids) // tr.cfg.batch_size
    warm = tr.train_epoch(max_iters=1)
    steady = [tr.train_epoch(max_iters=full) for _ in range(STEADY_EPOCHS)]
    iters = [i for ep in steady for i in ep.iters]
    losses = [warm.iters[0].loss] + [i.loss for i in iters]
    step_s = [i.t_compute for i in iters]  # dispatch .. device_get of loss
    wall_s = sum(ep.t_wall for ep in steady) / len(iters)
    misses = sum(int(ep.recompiles.get("misses", 0)) for ep in steady)
    print(f"{name}: first step (trace + compile + run) "
          f"{warm.iters[0].t_compute!r} s")
    print(f"{name}: {len(iters)} steady steps, s/step (dispatch to "
          f"device_get) {[round(s, 6) for s in step_s]}, median "
          f"{float(np.median(step_s))!r}; epoch wall / step {wall_s!r}; "
          f"recompiles {misses}")
    print(f"{name}: losses {losses}")
    if not np.isfinite(losses).all():
        raise SystemExit(f"chip_smoke: {name} loss is not finite: {losses}")
    return losses


def one_plan(tr):
    """Device arrays ``(feats, plan_arrays, labels)`` of epoch 0's first
    batch, padded to the trainer's high-water marks."""
    from repro.train.plan_io import stage_batch

    source = tr.plan_source_for(0, max_iters=1)
    try:
        batch = next(iter(source))
    finally:
        source.close()
    return stage_batch(batch.plan, batch.feats, batch.labels, batch.cache_plan)


def pallas_vs_jnp(tr) -> float:
    """Relative error of the Pallas split forward against the jnp one."""
    import jax

    from repro.core import sim_shuffle
    from repro.models.gnn.layers import gnn_forward

    feats, pa, _ = one_plan(tr)

    def forward(spec):
        fn = jax.jit(lambda p, f, a: gnn_forward(spec, p, f, a, sim_shuffle))
        return np.asarray(fn(tr.params, feats, pa))

    spec_p = tr.spec
    spec_j = dataclasses.replace(spec_p, agg_backend="jnp")
    return rel_err(forward(spec_p), forward(spec_j))


def one_chip_phase(ds, seed: int) -> None:
    t = time.perf_counter()
    split = make_trainer(ds, "split", "pallas", seed)
    print(f"split: presample + partition + init {time.perf_counter() - t!r} s")
    losses = train_phase("split", split)
    if not losses[-1] < losses[0]:
        raise SystemExit(
            f"chip_smoke: split loss did not fall: first {losses[0]!r}, "
            f"last {losses[-1]!r}"
        )
    train_phase("dp", make_trainer(ds, "dp", "jnp", seed))
    err = pallas_vs_jnp(split)
    print(f"split forward, pallas vs jnp on one plan: max |diff| / max |jnp| "
          f"= {err!r} (tolerance {PALLAS_VS_JNP_TOL})")
    if not err <= PALLAS_VS_JNP_TOL:
        raise SystemExit("chip_smoke: Pallas and jnp forwards disagree")


def split_updates(tr, mesh):
    """Two jitted ``(params, feats, plan_arrays, labels) -> (loss, grads,
    new_params)`` SGD updates of the trainer's model: the sim step (the P
    splits vmapped on one device) and the shard_map step on ``mesh``.

    The update is plain SGD at the trainer's learning rate. The trainer's
    Adam moves every weight by about lr * sign(g) on its first step, so a
    gradient entry near zero whose last bits differ would move by 2 * lr on
    one side; SGD keeps the update proportional to the gradient.
    """
    import jax

    from repro.core import sim_shuffle
    from repro.models.gnn.layers import gnn_forward, gnn_forward_split_mesh
    from repro.train import optimizer
    from repro.train.loss import masked_softmax_xent

    spec, opt = tr.spec, optimizer.sgd(tr.cfg.lr)

    def make(forward):
        @jax.jit
        def update(params, feats, pa, labels):
            loss, grads = jax.value_and_grad(
                lambda p: masked_softmax_xent(
                    forward(p, feats, pa), labels, pa["target_mask"]
                )
            )(params)
            new_params, _ = opt.update(grads, opt.init(params), params)
            return loss, grads, new_params

        return update

    sim = make(lambda p, f, a: gnn_forward(spec, p, f, a, sim_shuffle))
    spmd = make(lambda p, f, a: gnn_forward_split_mesh(spec, mesh, p, f, a))
    return sim, spmd


def four_chip_phase(ds, seed: int, devices) -> None:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.launch.sharding import make_split_mesh

    tr = make_trainer(ds, "split", "pallas", seed)
    feats, pa, labels = one_plan(tr)
    mesh = make_split_mesh(1, SPLITS, devices=devices)
    sim, spmd = split_updates(tr, mesh)
    args = (tr.params, feats, pa, labels)

    split = NamedSharding(mesh, PartitionSpec(mesh.axis_names[-1]))
    replicated = NamedSharding(mesh, PartitionSpec())
    spmd_args = jax.device_put(args, (replicated, split, split, split))
    results = {}
    for name, fn, fn_args in (
        ("sim", sim, jax.device_put(args, devices[0])),
        ("shard_map", spmd, spmd_args),
    ):
        t = time.perf_counter()
        out = jax.block_until_ready(fn(*fn_args))
        first = time.perf_counter() - t
        t = time.perf_counter()
        out = jax.block_until_ready(fn(*fn_args))
        print(f"{name}: first update (trace + compile + run) {first!r} s, "
              f"second {time.perf_counter() - t!r} s, loss {float(out[0])!r}")
        results[name] = jax.device_get(out)

    (l_sim, g_sim, p_sim), (l_spmd, g_spmd, p_spmd) = (
        results["sim"], results["shard_map"]
    )
    leaves = jax.tree_util.tree_leaves
    errs = {
        "loss": rel_err(l_spmd, l_sim),
        "grads": max(rel_err(a, b) for a, b in zip(leaves(g_spmd), leaves(g_sim))),
        "params": max(rel_err(a, b) for a, b in zip(leaves(p_spmd), leaves(p_sim))),
    }
    print(f"shard_map vs sim, max |diff| / max |sim| per leaf: {errs} "
          f"(tolerance {SPMD_VS_SIM_TOL})")
    finite = all(np.isfinite(x).all() for x in leaves(results["shard_map"]))
    if not finite or max(errs.values()) > SPMD_VS_SIM_TOL:
        raise SystemExit("chip_smoke: shard_map and sim updates disagree")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the shard_map split step across four chips")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the graph, features and weights")
    args = ap.parse_args(argv)

    devices = require_tpu(args.chips)
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    dev = devices[0]
    print(f"device: platform {dev.platform}, kind {dev.device_kind}, "
          f"count {len(devices)}")
    print(f"compile cache: {enable_compile_cache()}")
    print("times below are smoke timings of a few steps, not benchmark numbers")
    t = time.perf_counter()
    ds = orkut_dataset(args.seed)
    print(f"graph: {ds.graph.num_nodes} nodes, {ds.graph.num_edges} edges, "
          f"{len(ds.train_ids)} training vertices, built in "
          f"{time.perf_counter() - t!r} s")
    if args.chips == 4:
        four_chip_phase(ds, args.seed, devices)
    else:
        one_chip_phase(ds, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
    }}))


if __name__ == "__main__":
    main()
