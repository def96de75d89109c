"""Multi-device SPMD paths (shard_map shuffles, pjit sharding rules).

These spawn subprocesses with XLA_FLAGS so the main test process keeps a
single CPU device (smoke tests must never see 512 devices).
"""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code: str, devices: int = 8, timeout: int = 560) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=timeout,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


# one sampled split plan on the tiny graph, shared by the forward tests
_TINY_PLAN = """
        import jax, jax.numpy as jnp, numpy as np
        from repro.graph.datasets import make_dataset
        from repro.graph.sampling import sample_minibatch
        from repro.core import presample, partition_graph, build_split_plan, sim_shuffle
        from repro.launch.sharding import make_split_mesh
        from repro.models.gnn import GNNSpec, init_gnn_params
        from repro.models.gnn.layers import gnn_forward, gnn_forward_split_mesh
        from repro.train.plan_io import plan_to_device, load_features

        NDEV = 4
        ds = make_dataset("tiny")
        rng = np.random.default_rng(0)
        mb = sample_minibatch(ds.graph, ds.train_ids[:16], [3, 3], rng)
        w = presample(ds.graph, ds.train_ids, [3, 3], 16, num_epochs=1)
        part = partition_graph(ds.graph, NDEV, method="gsplit", weights=w)
        mesh = make_split_mesh(1, NDEV)
"""


def test_spmd_shuffle_matches_sim():
    """shard_map all-to-all shuffle == simulated shuffle, fwd and grad."""
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.core.shuffle import sim_shuffle, spmd_shuffle
        from repro.launch.sharding import make_split_mesh

        P_DEV, N, S, F = 4, 8, 3, 5
        mesh = make_split_mesh(1, P_DEV)
        rng = np.random.default_rng(0)
        h = jnp.asarray(rng.normal(size=(P_DEV, N, F)), jnp.float32)
        send_idx = jnp.asarray(
            rng.integers(0, N, size=(P_DEV, P_DEV, S)), jnp.int32)

        ref = sim_shuffle(h, send_idx)

        fn = jax.shard_map(
            lambda hl, si: spmd_shuffle(hl[0], si[0], "split")[None],
            mesh=mesh,
            in_specs=(P("split"), P("split")),
            out_specs=P("split"),
        )
        got = fn(h, send_idx)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-6)

        # gradients flow identically
        def loss_sim(h):
            return (sim_shuffle(h, send_idx) ** 2).sum()
        def loss_spmd(h):
            return (fn(h, send_idx) ** 2).sum()
        g1 = jax.grad(loss_sim)(h)
        g2 = jax.grad(loss_spmd)(h)
        np.testing.assert_allclose(np.asarray(g2), np.asarray(g1), rtol=1e-6)
        print("OK")
    """)


def test_spmd_gnn_forward_matches_sim():
    """Full split-parallel GNN forward under shard_map == sim mode."""
    _run(_TINY_PLAN + """
        plan = build_split_plan(mb, part.assignment, NDEV)
        pa = plan_to_device(plan)
        feats = jnp.asarray(load_features(plan, ds.features))

        spec = GNNSpec(model="sage", in_dim=ds.spec.feat_dim, hidden_dim=16,
                       out_dim=4, num_layers=2)
        params = init_gnn_params(jax.random.PRNGKey(0), spec)

        ref = gnn_forward(spec, params, feats, pa, sim_shuffle)
        got = gnn_forward_split_mesh(spec, mesh, params, feats, pa)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
        print("OK")
    """)


def test_spmd_gnn_forward_pallas_backend_matches_jnp():
    """The fused Pallas aggregation under shard_map == the jnp backend in sim
    mode — the layer-centric kernel is the same black box on both paths
    (docs/KERNELS.md)."""
    _run(_TINY_PLAN + """
        from dataclasses import replace

        plan = build_split_plan(mb, part.assignment, NDEV)
        pa = plan_to_device(plan)
        feats = jnp.asarray(load_features(plan, ds.features))

        for model in ("sage", "gcn", "gat"):
            spec = GNNSpec(model=model, in_dim=ds.spec.feat_dim, hidden_dim=16,
                           out_dim=4, num_layers=2, num_heads=2)
            spec_p = replace(spec, agg_backend="pallas")
            params = init_gnn_params(jax.random.PRNGKey(0), spec)
            ref = gnn_forward(spec, params, feats, pa, sim_shuffle)
            got = jax.jit(
                lambda f: gnn_forward_split_mesh(spec_p, mesh, params, f, pa)
            )(feats)
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                       rtol=5e-5, atol=5e-5)
            print(model, "OK")
    """)


def test_spmd_overlap_matches_sim():
    """The overlapped split-aggregation schedule under shard_map == sim mode
    (forward and gradients), for all three models, chunked, on both wire
    dtypes — the SpmdComm adapter must mirror SimComm exactly
    (DESIGN.md §3a)."""
    _run(_TINY_PLAN + """
        plan = build_split_plan(mb, part.assignment, NDEV, with_halves=True)
        pa = plan_to_device(plan, with_halves=True)
        feats = jnp.asarray(load_features(plan, ds.features))

        for model in ("sage", "gcn", "gat"):
            for wire in ("float32", "bfloat16"):
                spec = GNNSpec(model=model, in_dim=ds.spec.feat_dim,
                               hidden_dim=16, out_dim=4, num_layers=2,
                               num_heads=2, overlap=True, shuffle_chunks=2,
                               wire_dtype=wire)
                params = init_gnn_params(jax.random.PRNGKey(0), spec)
                ref = gnn_forward(spec, params, feats, pa, sim_shuffle)
                fn = jax.jit(
                    lambda h: gnn_forward_split_mesh(spec, mesh, params, h, pa)
                )
                got = fn(feats)
                np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                           rtol=2e-5, atol=2e-5)
                g1 = jax.grad(lambda h: (gnn_forward(
                    spec, params, h, pa, sim_shuffle) ** 2).sum())(feats)
                g2 = jax.grad(lambda h: (fn(h) ** 2).sum())(feats)
                np.testing.assert_allclose(np.asarray(g2), np.asarray(g1),
                                           rtol=2e-4, atol=2e-4)
                print(model, wire, "OK")
    """, devices=4)


def test_spmd_cache_serving_matches_sim():
    """shard_map cache serving (sharded resident block + all-to-all remote
    fetch) == sim serving == full host gather, and the cached spmd forward
    matches the sim forward fed by ``load_features``."""
    _run(_TINY_PLAN + """
        from jax.sharding import PartitionSpec as P
        from repro.core.shuffle import sim_serve_features, spmd_serve_features
        from repro.graph.cache import FeatureCache
        from repro.launch.sharding import split_cache_specs
        from repro.train.plan_io import cache_plan_to_device, load_miss_features

        plan = build_split_plan(mb, part.assignment, NDEV)
        cache = FeatureCache(ds.graph.num_nodes, NDEV, 24,
                             ranking=w.vertex_weight, mode="distributed",
                             partition_assignment=part.assignment)
        cp = cache.build_plan(plan)
        assert cp.breakdown().remote_hit > 0  # exercise the all-to-all
        block = jnp.asarray(cache.build_resident(ds.features))
        cpd = cache_plan_to_device(cp)
        miss = jnp.asarray(load_miss_features(cp, ds.features))

        want = load_features(plan, ds.features)
        ref = sim_serve_features(block, cpd, miss)
        np.testing.assert_array_equal(np.asarray(ref), want)

        specs = split_cache_specs((block, cpd, miss), split_axis="split")
        fn = jax.shard_map(
            lambda b, c, m: spmd_serve_features(
                b[0], jax.tree_util.tree_map(lambda x: x[0], c), m[0], "split"
            )[None],
            mesh=mesh, in_specs=specs, out_specs=P("split"),
        )
        got = fn(block, cpd, miss)
        np.testing.assert_array_equal(np.asarray(got), want)

        # cached spmd forward == sim forward on the host-gathered block
        spec = GNNSpec(model="sage", in_dim=ds.spec.feat_dim, hidden_dim=16,
                       out_dim=4, num_layers=2)
        params = init_gnn_params(jax.random.PRNGKey(0), spec)
        pa = plan_to_device(plan, cp)
        ref_out = gnn_forward(spec, params, jnp.asarray(want), pa, sim_shuffle)
        out = gnn_forward_split_mesh(spec, mesh, params, miss, pa,
                                     cache_block=block)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                                   rtol=2e-5, atol=2e-5)
        print("OK")
    """)


@pytest.mark.slow
def test_dryrun_one_combo_subprocess(tmp_path):
    """The dry-run driver lowers+compiles a full production combo (512 dev)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    out = subprocess.run(
        [
            sys.executable, "-m", "repro.launch.dryrun",
            "--arch", "smollm-135m", "--shape", "decode_32k",
            "--out", str(tmp_path),
        ],
        capture_output=True, text=True, env=env, timeout=560,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    assert "all dry-runs OK" in out.stdout


def test_production_mesh_shapes():
    _run("""
        from repro.launch.mesh import make_production_mesh, data_axes
        m1 = make_production_mesh()
        assert m1.axis_names == ("data", "model") and m1.size == 256
        m2 = make_production_mesh(multi_pod=True)
        assert m2.axis_names == ("pod", "data", "model") and m2.size == 512
        assert data_axes(m2) == ("pod", "data")
        print("OK")
    """, devices=512)
