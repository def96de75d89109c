"""2D (replica, split) mesh equivalence matrix (DESIGN.md §9).

Every new mesh code path reduces to an already-trusted one:

  * R=1 mesh  == the 1D split path, bit for bit — all models, backends,
    schedules, wire dtypes, including repadded (HWM-grown) plans.
  * R×1 mesh  == the ``dp`` baseline at the same global batch/seed, within
    documented fp tolerance (joint masked mean vs mean of per-replica
    means: equal target counts make them equal in exact arithmetic; only
    the reassociation differs).
  * psum'd gradients on the (R, P) mesh == hand-averaged per-replica
    gradients, exactly.
  * spmd on a 2×2 mesh == per-replica sim, fwd + grad (subprocess with
    ``--xla_force_host_platform_device_count=4``).
  * steady state at fixed caps recompiles nothing under R=2 for the
    serial/pipelined/device plan sources (the PR 7 tracer contract).
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.shuffle import SimComm, sim_alltoall
from repro.graph.datasets import make_dataset
from repro.models.gnn import GNNSpec
from repro.runtime import MeshPlanBatch, mesh_signature, plan_signature
from repro.train.trainer import TrainConfig, Trainer

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


@pytest.fixture(scope="module")
def ds():
    return make_dataset("tiny")


def _spec(ds, model="sage", backend="jnp"):
    return GNNSpec(
        model=model, in_dim=ds.spec.feat_dim, hidden_dim=16,
        out_dim=ds.spec.num_classes, num_layers=2, num_heads=2,
        agg_backend=backend,
    )


def _cfg(num_replicas, **kw):
    base = dict(
        mode="split", num_devices=2, fanouts=(3, 3), batch_size=32,
        presample_epochs=1, plan_source="serial", seed=7,
        num_replicas=num_replicas,
    )
    base.update(kw)
    return TrainConfig(**base)


def _trajectory(ds, spec, cfg, epochs=2, iters=2):
    tr = Trainer(ds, spec, cfg)
    traj = []
    for _ in range(epochs):
        st = tr.train_epoch(max_iters=iters)
        traj += [(i.loss, i.accuracy) for i in st.iters]
    return tr, traj


def _params_equal(a, b) -> bool:
    return all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b))
    )


# --------------------------------------------------------------------- #
# R=1 mesh == 1D split path, bit for bit
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("model", ["sage", "gcn", "gat"])
def test_r1_mesh_bitwise_identical_to_1d(ds, model):
    """The degenerate mesh reduces to the trusted 1D path exactly, across
    the full backend × schedule × wire matrix (two epochs, so epoch-2 plans
    are repadded against epoch-1 high-water marks)."""
    for backend in ("jnp", "pallas"):
        for overlap in (False, True):
            for wire in ("float32", "bfloat16"):
                spec = _spec(ds, model=model, backend=backend)
                kw = dict(shuffle_overlap=overlap, wire_dtype=wire)
                tr0, t0 = _trajectory(ds, spec, _cfg(0, **kw))
                tr1, t1 = _trajectory(ds, spec, _cfg(1, **kw))
                combo = (model, backend, overlap, wire)
                assert len(t0) == len(t1) > 0, combo
                assert t0 == t1, combo  # exact float equality
                assert _params_equal(tr0.params, tr1.params), combo


def test_r1_mesh_bitwise_with_cache_and_replication(ds):
    """The cached mesh step and the replicated-block attachment also reduce
    to the 1D path bit for bit."""
    spec = _spec(ds)
    kw = dict(
        cache_mode="distributed", cache_capacity_per_device=24,
        replication_budget=0.05,
    )
    tr0, t0 = _trajectory(ds, spec, _cfg(0, **kw))
    tr1, t1 = _trajectory(ds, spec, _cfg(1, **kw))
    assert t0 == t1
    assert _params_equal(tr0.params, tr1.params)
    assert tr1.cache_block is not None  # the cached mesh step actually ran
    assert tr1.rep_block is not None


def test_r1_mesh_bitwise_on_inline_path_with_forced_repad(ds):
    """``train_iter`` (the inline step path) under the mesh, with a batch
    sequence engineered so the second plan is HWM-grown: a big batch first
    raises every mark, then a small batch must be repadded up to them."""
    spec = _spec(ds)
    results = []
    for r in (0, 1):
        tr = Trainer(ds, spec, _cfg(r))
        big = ds.train_ids[:48]
        small = ds.train_ids[48:60]
        s1 = tr.train_iter(big)
        hwm_after_big = dict(tr._pad_hwm)
        s2 = tr.train_iter(small)
        # the small batch really was grown to the big batch's marks
        assert tr._pad_hwm == hwm_after_big
        results.append((s1.loss, s1.accuracy, s2.loss, s2.accuracy))
    assert results[0] == results[1]


def test_mesh_pipelined_matches_serial(ds):
    """serial == pipelined extends to mesh deliveries (R=2): same keyed
    RNG, same shared-HWM repadding on the ordered side of the queue."""
    spec = _spec(ds)
    _, serial = _trajectory(ds, spec, _cfg(2, plan_source="serial"))
    _, pipelined = _trajectory(
        ds, spec, _cfg(2, plan_source="pipelined", pipeline_depth=3,
                       plan_workers=2)
    )
    assert len(serial) == len(pipelined) > 0
    assert serial == pipelined


# --------------------------------------------------------------------- #
# replica-axis gradient sync
# --------------------------------------------------------------------- #
def test_rx1_mesh_matches_dp_trajectory(ds):
    """R×1 split-degenerate mesh == ``dp`` over R devices at the same
    global batch and seed. The replica chunks and their sampled subgraphs
    are identical by keying (``sample_micro_batch``); dp computes one joint
    masked mean where the mesh averages R per-replica means — equal target
    counts (batch 32, R=2 -> 16/16) make those equal up to fp
    reassociation, hence the tolerance instead of bit-equality."""
    spec = _spec(ds)
    _, mesh_traj = _trajectory(
        ds, spec, _cfg(2, num_devices=1), epochs=2, iters=3
    )
    cfg_dp = TrainConfig(
        mode="dp", num_devices=2, fanouts=(3, 3), batch_size=32,
        presample_epochs=1, plan_source="serial", seed=7,
    )
    _, dp_traj = _trajectory(ds, spec, cfg_dp, epochs=2, iters=3)
    assert len(mesh_traj) == len(dp_traj) > 0
    np.testing.assert_allclose(
        [l for l, _ in mesh_traj], [l for l, _ in dp_traj],
        rtol=2e-4, atol=1e-5,
    )
    np.testing.assert_allclose(
        [a for _, a in mesh_traj], [a for _, a in dp_traj], atol=1e-6
    )


def test_replica_psum_equals_hand_average_subprocess():
    """psum'd gradient pytree on a (2, 2) mesh == the hand-averaged
    per-replica gradients, exactly (fixed reduction order)."""
    _run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.core.shuffle import replica_grad_mean
        from repro.launch.sharding import make_split_mesh

        R_DEV, P_DEV = 2, 2
        mesh = make_split_mesh(R_DEV, P_DEV)
        assert mesh.axis_names == ("replica", "split") and mesh.size == 4
        rng = np.random.default_rng(0)
        grads = {
            "w": jnp.asarray(rng.normal(size=(R_DEV, P_DEV, 3, 5)), jnp.float32),
            "b": jnp.asarray(rng.normal(size=(R_DEV, P_DEV, 5)), jnp.float32),
        }

        def body(gl):
            g = jax.tree_util.tree_map(lambda x: x[0, 0], gl)
            out = replica_grad_mean(g, "replica", R_DEV)
            return jax.tree_util.tree_map(lambda x: x[None, None], out)

        fn = jax.shard_map(
            body, mesh=mesh,
            in_specs=P("replica", "split"), out_specs=P("replica", "split"),
        )
        got = fn(grads)
        for k in grads:
            g = np.asarray(grads[k])
            want = (g[0] + g[1]) / 2.0  # hand average, replica order
            for r in range(R_DEV):
                np.testing.assert_array_equal(np.asarray(got[k])[r], want)
        print("OK")
    """)


# --------------------------------------------------------------------- #
# spmd == sim on the 2x2 mesh, fwd + grad
# --------------------------------------------------------------------- #
def test_spmd_2x2_mesh_matches_sim_subprocess():
    """Full split-parallel forward + params-grad on a real 2×2 device mesh
    == per-replica sim. The all_to_all over the ``split`` axis must stay
    confined to each replica group — any leakage across the replica axis
    corrupts the forward, so the fwd assert *is* the locality check."""
    _run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.core import (
            presample, partition_graph, build_split_plan, sim_shuffle,
        )
        from repro.core.splitting import repad_plan
        from repro.graph.datasets import make_dataset
        from repro.launch.sharding import make_split_mesh, mesh_plan_specs
        from repro.models.gnn import GNNSpec, init_gnn_params
        from repro.models.gnn.layers import gnn_forward, gnn_forward_spmd
        from repro.train.plan_io import plan_to_device, load_features

        R_DEV, P_DEV = 2, 2
        ds = make_dataset("tiny")
        w = presample(ds.graph, ds.train_ids, [3, 3], 16, num_epochs=1)
        part = partition_graph(ds.graph, P_DEV, method="gsplit", weights=w)

        # two per-replica plans (the producer's R>1 keying), repadded to
        # shared high-water marks twice so the stack is rectangular
        from repro.graph.sampling import NeighborSampler
        sampler = NeighborSampler(ds.graph, ds.train_ids, [3, 3], 32, seed=7)
        samples = sampler.sample_micro_batch(
            sampler.epoch_targets(0)[0], R_DEV, epoch=0, batch=0
        )
        plans = [
            build_split_plan(s, part.assignment, P_DEV) for s in samples
        ]
        hwm = {}
        for _ in range(2):
            for p in plans:
                repad_plan(p, hwm)

        pa_parts = [plan_to_device(p) for p in plans]
        feat_parts = [
            jnp.asarray(load_features(p, ds.features)) for p in plans
        ]
        pa = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *pa_parts
        )  # leaves (R, P, ...)
        feats = jnp.stack(feat_parts)

        spec = GNNSpec(model="sage", in_dim=ds.spec.feat_dim, hidden_dim=16,
                       out_dim=4, num_layers=2)
        params = init_gnn_params(jax.random.PRNGKey(0), spec)

        mesh = make_split_mesh(R_DEV, P_DEV)
        pa_specs = mesh_plan_specs(pa)

        def body(params, feats_l, pa_l):
            pa_dev = jax.tree_util.tree_map(lambda x: x[0, 0], pa_l)
            out = gnn_forward_spmd(
                spec, params, feats_l[0, 0], pa_dev, "split"
            )
            return out[None, None]

        fn = jax.jit(jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(), P("replica", "split"), pa_specs),
            out_specs=P("replica", "split"),
            check_vma=False,
        ))
        got = fn(params, feats, pa)

        refs = [
            gnn_forward(spec, params, f, p, sim_shuffle)
            for f, p in zip(feat_parts, pa_parts)
        ]
        for r in range(R_DEV):
            np.testing.assert_allclose(
                np.asarray(got[r]), np.asarray(refs[r]),
                rtol=2e-5, atol=2e-5,
            )

        # grad wrt params of the replica-mean loss, spmd == sim
        def loss_spmd(params):
            out = fn(params, feats, pa)
            return sum((out[r] ** 2).sum() for r in range(R_DEV)) / R_DEV

        def loss_sim(params):
            outs = [
                gnn_forward(spec, params, f, p, sim_shuffle)
                for f, p in zip(feat_parts, pa_parts)
            ]
            return sum((o ** 2).sum() for o in outs) / R_DEV

        g_spmd = jax.grad(loss_spmd)(params)
        g_sim = jax.grad(loss_sim)(params)
        for a, b in zip(
            jax.tree_util.tree_leaves(g_spmd),
            jax.tree_util.tree_leaves(g_sim),
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4
            )
        print("OK")
    """)


def _run_sub(code: str, devices: int = 4, timeout: int = 560) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=timeout,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


# --------------------------------------------------------------------- #
# sim-mode replica-group locality (the axis argument)
# --------------------------------------------------------------------- #
def test_sim_alltoall_axis1_confined_per_replica():
    """A replica-batched sim all-to-all (axis=1) == stacking per-replica
    exchanges: no row ever crosses the replica axis."""
    rng = np.random.default_rng(0)
    send = jnp.asarray(rng.normal(size=(3, 4, 4, 5, 2)), jnp.float32)
    got = sim_alltoall(send, axis=1)
    want = jnp.stack([sim_alltoall(send[r]) for r in range(3)])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_simcomm_axis1_matches_per_replica_adapter():
    """The replica-batched SimComm(axis=1) == the classic SimComm applied
    per replica, for every adapter hook."""
    rng = np.random.default_rng(1)
    R, P, N, S, F = 2, 3, 8, 4, 5
    rows = jnp.asarray(rng.normal(size=(R, P, N, F)), jnp.float32)
    send_idx = jnp.asarray(rng.integers(0, N, size=(R, P, P, S)), jnp.int32)
    extra = jnp.asarray(rng.normal(size=(6, F)), jnp.float32)

    c2d = SimComm(axis=1)
    c1d = SimComm()
    send = c2d.send_gather(rows, send_idx)
    recv = c2d.exchange(send, "float32")
    appended = c2d.append_rows(rows, extra)
    for r in range(R):
        send_r = c1d.send_gather(rows[r], send_idx[r])
        np.testing.assert_array_equal(np.asarray(send[r]), np.asarray(send_r))
        np.testing.assert_array_equal(
            np.asarray(recv[r]), np.asarray(c1d.exchange(send_r, "float32"))
        )
        np.testing.assert_array_equal(
            np.asarray(appended[r]),
            np.asarray(c1d.append_rows(rows[r], extra)),
        )
    with pytest.raises(ValueError):
        SimComm(axis=2)


# --------------------------------------------------------------------- #
# signatures + recompiles across mesh shapes
# --------------------------------------------------------------------- #
def test_mesh_signature_keys_on_mesh_shape(ds):
    """Signatures separate by mesh shape: the R=1 mesh key differs from the
    1D key of the same plan, and R=1 differs from R=2."""
    spec = _spec(ds)
    tr = Trainer(ds, spec, _cfg(2))
    source = tr.plan_source_for(0, max_iters=1)
    batch = next(iter(source))
    source.close()
    assert isinstance(batch, MeshPlanBatch) and batch.num_replicas == 2
    parts = [(p.plan, p.cache_plan) for p in batch.parts]
    sig2 = mesh_signature(parts, ("x",))
    sig1 = mesh_signature(parts[:1], ("x",))
    flat = plan_signature(parts[0][0], parts[0][1], ("x",))
    assert sig2 != sig1
    assert sig1 != flat and sig2 != flat
    assert sig2[0] == "mesh" and sig2[1] == 2
    # rectangular across the replica axis: delivery repadded both parts to
    # the shared marks, so the per-part signatures coincide
    assert sig2[2][0] == sig2[2][1]


@pytest.mark.parametrize("source", ["serial", "pipelined", "device"])
def test_mesh_no_steady_state_recompiles(ds, source):
    """The PR 7 zero-steady-state-recompile contract extends to R=2: after
    warmup, an epoch at fixed caps never retraces the mesh step."""
    spec = _spec(ds)
    cfg = _cfg(
        2, plan_source=source, pipeline_depth=3, plan_workers=2,
        sampler_backend="jnp", trace_recompiles=True,
        presample_epochs=2,
    )
    tr = Trainer(ds, spec, cfg)
    last = None
    for _ in range(4):  # HWM caps only grow; they settle within warmup
        last = tr.train_epoch(max_iters=3)
    assert last.recompiles["steps"] == len(last.iters) > 0
    assert last.recompiles["misses"] == 0, last.recompiles
    # the probe is live and it really was the mesh step that compiled
    assert tr.recompiles.total_misses > 0
    warm = tr.recompiles.summary()["by_fn"]
    assert "mesh_step" in warm


# --------------------------------------------------------------------- #
# keying + validation
# --------------------------------------------------------------------- #
def test_device_sampler_replica_keying_flattens_batch_counter(ds):
    """Replica fan-out keys the device engine on ``batch*R + replica`` —
    the same draw another caller would get from the flattened counter —
    and defaults leave the legacy key untouched."""
    from repro.core import partition_graph, presample
    from repro.graph.sampling import NeighborSampler
    from repro.sampler import DeviceSampler

    w = presample(ds.graph, ds.train_ids, [3, 3], 16, num_epochs=1)
    part = partition_graph(ds.graph, 2, method="gsplit", weights=w)
    host = NeighborSampler(ds.graph, ds.train_ids, [3, 3], 32, seed=7)
    eng = DeviceSampler(
        ds.graph, part.assignment, 2, [3, 3], 7, host_sampler=host,
        backend="jnp",
    )
    t = ds.train_ids[:16]
    a = eng.sample_batch(t, epoch=0, batch=1, replica=1, num_replicas=2)
    b = eng.sample_batch(t, epoch=0, batch=3)  # 1*2 + 1
    for la, lb in zip(a.layers, b.layers):
        np.testing.assert_array_equal(la.src, lb.src)
        np.testing.assert_array_equal(la.dst, lb.dst)
    with pytest.raises(ValueError):
        eng.sample_batch(t, epoch=0, batch=0, replica=2, num_replicas=2)


def test_mesh_rejects_non_split_modes(ds):
    spec = _spec(ds)
    with pytest.raises(ValueError, match="split"):
        Trainer(
            ds, spec,
            TrainConfig(mode="dp", num_devices=2, fanouts=(3, 3),
                        batch_size=32, num_replicas=2),
        )
