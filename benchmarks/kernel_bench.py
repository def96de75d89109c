"""Per-kernel microbenchmarks: Pallas (interpret mode) vs jnp oracle.

Interpret-mode wall time is NOT TPU time; the derived columns therefore
report the *modeled* HBM traffic of each formulation (converted to seconds
with the TPU v5e bandwidth from ``launch/roofline.py``) next to the measured
CPU wall time. The fused gather->segsum sweep is the headline: it shows the
redundancy-vs-bandwidth trade of docs/KERNELS.md — the fused kernel re-reads
the (M, F) mixed buffer once per destination row-block instead of streaming
the (E, F) per-edge buffer three times, so it wins exactly when the average
per-block degree E/(DB*M) exceeds ~1/3 (high fan-out), and the crossover is
visible in the ``fanout`` sweep.

``--smoke`` runs one tiny configuration of every arm and exits non-zero if
any output contains NaN/Inf — the CI gate for kernel numeric regressions.
"""
from __future__ import annotations

import sys

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import Row, timeit
from repro.kernels.edge_softmax.ops import edge_softmax_pallas
from repro.kernels.edge_softmax.ref import edge_softmax_ref
from repro.kernels.gather_segsum import layout
from repro.kernels.gather_segsum.ops import (
    gather_segment_sum,
    gather_weighted_segsum,
)
from repro.kernels.gather_segsum.ref import (
    gather_segment_sum_ref,
    gather_weighted_segsum_ref,
)
from repro.kernels.segsum.ops import segment_sum_pallas
from repro.kernels.segsum.ref import segment_sum_ref
from repro.launch.roofline import HBM_BW


def _fused_case(rng, N, fanout, F, M=None):
    """Random aggregation problem shaped like one GNN layer transition."""
    M = N if M is None else M
    E = N * fanout
    dst = np.repeat(np.arange(N, dtype=np.int32), fanout)
    src = rng.integers(0, M, size=E).astype(np.int32)
    mask = rng.random(E) > 0.05
    mixed = jnp.asarray(rng.normal(size=(M, F)), jnp.float32)
    lay = layout.layer_layout(dst[None], mask[None], N)
    return mixed, src, dst, mask, lay


def modeled_bytes(E, M, F, N, lay, itemsize=4):
    """Logical HBM traffic (bytes) of the two formulations.

    unfused (jnp): gather writes the (E, F) buffer, the scatter-add reads it
    back and reads/writes the output once more -> (M + 3E + N) * F rows of
    traffic plus the (E,) index streams.

    fused (pallas): the mixed buffer is re-read once per destination
    row-block (DB * M * F — the *redundancy* side of the trade), the output
    is written once, and the packed index streams ride along. The per-edge
    buffer never exists.
    """
    DB, EB = lay["pack_perm"].shape[1:]
    unfused = (M + 3 * E + N) * F * itemsize + 2 * E * 4
    fused = (DB * M + N) * F * itemsize + 2 * DB * EB * 4 + E * 4
    return unfused, fused


def _fused_rows(smoke: bool) -> list[Row]:
    rows = []
    rng = np.random.default_rng(0)
    sweep = (
        [(128, 8, 64)]
        if smoke
        else [(512, 4, 128), (512, 16, 128), (512, 64, 128), (512, 16, 256),
              (2048, 16, 128)]
    )
    for N, fanout, F in sweep:
        mixed, src, dst, mask, lay = _fused_case(rng, N, fanout, F)
        E, M = dst.shape[0], mixed.shape[0]
        pp = jnp.asarray(lay["pack_perm"][0])
        pd = jnp.asarray(lay["pack_dst"][0])
        srcj, dstj, maskj = jnp.asarray(src), jnp.asarray(dst), jnp.asarray(mask)

        jnp_fn = jax.jit(
            lambda m: gather_segment_sum_ref(m, srcj, dstj, maskj, N)
        )
        fused_fn = jax.jit(
            lambda m: gather_segment_sum(m, srcj, pp, pd, N, interpret=True)
        )
        t_jnp = timeit(lambda: jax.block_until_ready(jnp_fn(mixed)), iters=2)
        t_fus = timeit(lambda: jax.block_until_ready(fused_fn(mixed)), iters=2)
        out_j, out_f = np.asarray(jnp_fn(mixed)), np.asarray(fused_fn(mixed))
        if not (np.isfinite(out_j).all() and np.isfinite(out_f).all()):
            raise SystemExit(
                f"NaN/Inf in gather_segsum bench output (N={N} fanout={fanout})"
            )
        np.testing.assert_allclose(out_f, out_j, rtol=5e-5, atol=5e-5)
        b_unf, b_fus = modeled_bytes(E, M, F, N, lay)
        rows.append(Row(
            f"kernel/gather_segsum/jnp_E{E}_F{F}_fan{fanout}", t_jnp * 1e6,
            f"bytes={b_unf:.3e} v5e_hbm_est={b_unf / HBM_BW * 1e6:.2f}us",
        ))
        rows.append(Row(
            f"kernel/gather_segsum/fused_E{E}_F{F}_fan{fanout}", t_fus * 1e6,
            f"bytes={b_fus:.3e} v5e_hbm_est={b_fus / HBM_BW * 1e6:.2f}us "
            f"bytes_ratio={b_unf / b_fus:.2f}",
        ))

    # softmax-weighted variant (the GAT aggregation)
    N, fanout, H, dh = (64, 4, 2, 16) if smoke else (512, 16, 4, 32)
    mixed, src, dst, mask, lay = _fused_case(rng, N, fanout, H * dh)
    w = jnp.asarray(rng.random((dst.shape[0], H)), jnp.float32)
    pp = jnp.asarray(lay["pack_perm"][0])
    pd = jnp.asarray(lay["pack_dst"][0])
    srcj, dstj, maskj = jnp.asarray(src), jnp.asarray(dst), jnp.asarray(mask)
    jnp_fn = jax.jit(
        lambda m: gather_weighted_segsum_ref(m, w, srcj, dstj, maskj, N)
    )
    fused_fn = jax.jit(lambda m: gather_weighted_segsum(m, w, srcj, pp, pd, N,
                                                       interpret=True))
    t_jnp = timeit(lambda: jax.block_until_ready(jnp_fn(mixed)), iters=2)
    t_fus = timeit(lambda: jax.block_until_ready(fused_fn(mixed)), iters=2)
    out_j, out_f = np.asarray(jnp_fn(mixed)), np.asarray(fused_fn(mixed))
    if not (np.isfinite(out_j).all() and np.isfinite(out_f).all()):
        raise SystemExit("NaN/Inf in weighted gather_segsum bench output")
    np.testing.assert_allclose(out_f, out_j, rtol=5e-5, atol=5e-5)
    rows.append(Row(
        f"kernel/gather_segsum_weighted/jnp_H{H}", t_jnp * 1e6, ""))
    rows.append(Row(
        f"kernel/gather_segsum_weighted/fused_H{H}", t_fus * 1e6, ""))
    return rows


def _legacy_rows(smoke: bool) -> list[Row]:
    rows = []
    rng = np.random.default_rng(0)
    E, F, N = (1024, 64, 256) if smoke else (16384, 256, 4096)
    contrib = jnp.asarray(rng.normal(size=(E, F)), jnp.float32)
    dst = rng.integers(0, N, size=E).astype(np.int32)
    mask = np.ones(E, bool)

    t_ref = timeit(
        lambda: jax.block_until_ready(
            segment_sum_ref(contrib, jnp.asarray(dst), jnp.asarray(mask), N)
        )
    )
    t_pal = timeit(
        lambda: jax.block_until_ready(segment_sum_pallas(contrib, dst, mask, N, interpret=True))
    )
    out = np.asarray(segment_sum_pallas(contrib, dst, mask, N, interpret=True))
    if not np.isfinite(out).all():
        raise SystemExit("NaN/Inf in segsum bench output")
    flops = 2 * E * F  # one MAC per (edge, feature)
    rows.append(Row("kernel/segsum/jnp", t_ref * 1e6,
                    f"E={E} F={F} N={N} flops={flops:.2e}"))
    rows.append(Row("kernel/segsum/pallas_interpret", t_pal * 1e6,
                    f"v5e_mxu_est={flops/197e12*1e6:.3f}us"))

    H = 8
    logits = jnp.asarray(rng.normal(size=(E, H)), jnp.float32)
    t_ref = timeit(
        lambda: jax.block_until_ready(
            edge_softmax_ref(logits, jnp.asarray(dst), jnp.asarray(mask), N)
        )
    )
    t_pal = timeit(
        lambda: jax.block_until_ready(edge_softmax_pallas(logits, dst, mask, N, interpret=True))
    )
    out = np.asarray(edge_softmax_pallas(logits, dst, mask, N, interpret=True))
    if not np.isfinite(out).all():
        raise SystemExit("NaN/Inf in edge_softmax bench output")
    rows.append(Row("kernel/edge_softmax/jnp", t_ref * 1e6, f"E={E} H={H}"))
    rows.append(Row("kernel/edge_softmax/pallas_interpret", t_pal * 1e6, ""))
    return rows


def run(smoke: bool = False) -> list[Row]:
    return _legacy_rows(smoke) + _fused_rows(smoke)


if __name__ == "__main__":
    smoke = "--smoke" in sys.argv
    print("name,us_per_call,derived")
    for row in run(smoke=smoke):
        print(row.csv(), flush=True)
    print("# kernel_bench OK (all outputs finite)")
