"""The three-layer GAT (hidden layers of concatenated heads, a skip across
the middle layer, averaged output heads) computes the same loss and
gradients on every path: the split plan (sim), the overlap schedule and
the fused Pallas aggregation (interpret mode on the CPU), each against the
single-device data-parallel plan."""
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import build_split_plan, partition_graph, presample, sim_shuffle
from repro.graph.datasets import make_dataset
from repro.graph.sampling import sample_minibatch
from repro.models.gnn import GNNSpec, init_gnn_params
from repro.models.gnn.layers import gnn_forward
from repro.train.loss import masked_softmax_xent
from repro.train.plan_io import load_features, load_labels, plan_to_device

NDEV = 4
FANOUTS = [4, 4, 4]


@pytest.fixture(scope="module")
def setup():
    ds = make_dataset("tiny")  # 16 features
    rng = np.random.default_rng(11)
    mb = sample_minibatch(ds.graph, ds.train_ids[:32], FANOUTS, rng)
    w = presample(ds.graph, ds.train_ids, FANOUTS, 32, num_epochs=2)
    part = partition_graph(ds.graph, NDEV, method="gsplit", weights=w)
    # 16 -> 4 heads of 8 -> (skip) 4 heads of 8 -> 4 heads of 4 classes, averaged
    spec = GNNSpec(model="gat", in_dim=ds.spec.feat_dim, hidden_dim=32,
                   out_dim=ds.spec.num_classes, num_layers=3, num_heads=4)
    return ds, mb, part, spec


def _loss_and_grads(ds, spec, params, plan, with_halves=False):
    pa = plan_to_device(plan, with_halves=with_halves)
    feats = jnp.asarray(load_features(plan, ds.features))
    labels = jnp.asarray(load_labels(plan, ds.labels))

    def f(p):
        logits = gnn_forward(spec, p, feats, pa, sim_shuffle)
        return masked_softmax_xent(logits, labels, pa["target_mask"])

    return jax.value_and_grad(f)(params)


def test_layout_has_the_published_shape(setup):
    _, _, _, spec = setup
    params = init_gnn_params(jax.random.PRNGKey(0), spec)
    assert [p["w"].shape for p in params] == [(16, 4, 8), (32, 4, 8), (32, 4, 4)]
    assert [p["b"].shape for p in params] == [(32,), (32,), (4,)]


@pytest.mark.parametrize("path", ["split", "overlap", "pallas"])
def test_gat_path_matches_dp(setup, path):
    ds, mb, part, spec = setup
    params = init_gnn_params(jax.random.PRNGKey(3), spec)
    single = np.zeros(ds.graph.num_nodes, dtype=np.int32)
    l_dp, g_dp = _loss_and_grads(ds, spec, params, build_split_plan(mb, single, 1))
    halves = path == "overlap"
    plan = build_split_plan(mb, part.assignment, NDEV, with_halves=halves)
    other = {
        "split": spec,
        "overlap": replace(spec, overlap=True, shuffle_chunks=2, wire_dtype="float32"),
        "pallas": replace(spec, agg_backend="pallas"),
    }[path]
    l_got, g_got = _loss_and_grads(ds, other, params, plan, with_halves=halves)
    # float32: the paths sum each destination's edges in another order (split
    # halves, packed tiles), so they agree to rounding, as the two-layer
    # cross-path tests hold them
    np.testing.assert_allclose(float(l_got), float(l_dp), rtol=2e-5, atol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(g_got), jax.tree_util.tree_leaves(g_dp)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5)
