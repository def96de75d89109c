"""Compile the main path's Pallas kernels for a TPU v5e chip, at paper widths.

Nothing runs: each test lowers a kernel with ``interpret=False`` for one
device of a described ``v5e:2x2`` topology and compiles it with the TPU
compiler, which refuses what Mosaic cannot lower (unaligned slices, layouts,
too much VMEM) without a chip attached. The interpret-mode tests check the
numbers; these check that the same kernels compile for the chip.

The topology is described only inside the fixture below: the TPU library
can be loaded by one process at a time, so no module-level code, ``skipif``
or ``parametrize`` argument may touch it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.gather_segsum import ops
from repro.sampler.kernel import wavefront_expand_kernel

# one split's bottom layer at batch 1024, fan-out 15 on the Orkut-shaped graph
M, N, E = 8192, 8192, 65536  # mixed rows, dst rows, edges
DB, EB = 64, 2048  # dst blocks of 128 rows, packed edge slots per block
FRONTIER = 4096  # sampler rows per wavefront call


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a described chip's compile cannot be read back from the persistent
    # cache without a chip, so the cache stays off around these compiles
    enabled = jax.config.jax_enable_compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            try:
                topo = topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2"
                )
            except Exception as e:  # noqa: BLE001 — any failure means no topology
                pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


def _compile_for(sharding, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "the kernel was not compiled with Mosaic"


def _layout_shapes():
    i32 = jnp.int32
    return [((E,), i32), ((DB, EB), i32), ((DB, EB), i32)]


@pytest.mark.parametrize("F", [512, 256])
def test_gather_segment_mean_fwd_bwd_compiles(one_chip, F):
    def value_and_grad(mixed, edge_src, pack_perm, pack_dst, seg_offsets):
        return jax.value_and_grad(
            lambda m: ops.gather_segment_mean(
                m, edge_src, pack_perm, pack_dst, seg_offsets, N,
                interpret=False,
            ).sum()
        )(mixed)

    _compile_for(
        one_chip, value_and_grad, ((M, F), jnp.float32), *_layout_shapes(),
        ((N + 1,), jnp.int32),
    )


def test_gather_weighted_segsum_fwd_bwd_compiles(one_chip):
    H, F = 4, 256

    def value_and_grad(mixed, weights, edge_src, pack_perm, pack_dst):
        return jax.value_and_grad(
            lambda m, w: ops.gather_weighted_segsum(
                m, w, edge_src, pack_perm, pack_dst, N, interpret=False
            ).sum(),
            argnums=(0, 1),
        )(mixed, weights)

    _compile_for(
        one_chip, value_and_grad, ((M, F), jnp.float32),
        ((E, H), jnp.float32), *_layout_shapes(),
    )


def test_sampler_wavefront_expand_compiles(one_chip):
    _compile_for(
        one_chip,
        lambda vid, deg, key: wavefront_expand_kernel(
            vid, deg, key, fanout=15, interpret=False
        ),
        ((FRONTIER,), jnp.int32), ((FRONTIER,), jnp.int32),
        ((1, 2), jnp.uint32),
    )
