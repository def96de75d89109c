"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.testing import given, settings, st  # hypothesis or fallback

HAVE_HYPOTHESIS = True  # repro.testing provides a deterministic fallback

from repro.kernels import pallas_interpret, segment_ops
from repro.kernels.edge_softmax.ops import edge_softmax_pallas
from repro.kernels.edge_softmax.ref import edge_softmax_ref
from repro.kernels.segsum.ops import pack_edges, segment_sum_pallas
from repro.kernels.segsum.ref import segment_sum_ref

SHAPES = [
    (64, 16, 32),
    (1000, 64, 300),
    (37, 130, 10),  # non-aligned feature dim
    (4096, 256, 1024),
    (5, 8, 513),  # tiny edges, many segments
    (513, 1, 127),  # single feature
]


@pytest.mark.parametrize("platform, interpret", [("cpu", True), ("tpu", False)])
def test_pallas_interpret_follows_platform(monkeypatch, platform, interpret):
    monkeypatch.setattr("jax.default_backend", lambda: platform)
    assert pallas_interpret() is interpret


def test_pallas_interpret_refuses_other_platforms(monkeypatch):
    monkeypatch.setattr("jax.default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        pallas_interpret()


@pytest.mark.parametrize("E,F,N", SHAPES)
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_segment_sum_matches_ref(E, F, N, dtype):
    rng = np.random.default_rng(E + F)
    contrib = jnp.asarray(rng.normal(size=(E, F)), dtype)
    dst = rng.integers(0, N, size=E).astype(np.int32)
    mask = rng.random(E) > 0.1
    out = segment_sum_pallas(contrib, dst, mask, N, interpret=True)
    if dtype == np.float32:
        ref = segment_sum_ref(contrib, jnp.asarray(dst), jnp.asarray(mask), N)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
        )
    else:
        # the kernel accumulates in f32 (preferred_element_type) and casts
        # once; compare against the f32-accumulated oracle within bf16
        # output quantization (~0.4% relative)
        ref = segment_sum_ref(
            contrib.astype(jnp.float32), jnp.asarray(dst), jnp.asarray(mask), N
        )
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref),
            rtol=1e-2, atol=0.3,
        )


@pytest.mark.parametrize("E,H,N", [(1000, 4, 300), (64, 8, 16), (7, 1, 129),
                                   (2048, 3, 700)])
def test_edge_softmax_matches_ref(E, H, N):
    rng = np.random.default_rng(E + H)
    logits = jnp.asarray(rng.normal(size=(E, H)) * 3, jnp.float32)
    dst = rng.integers(0, N, size=E).astype(np.int32)
    mask = rng.random(E) > 0.15
    out = edge_softmax_pallas(logits, dst, mask, N, interpret=True)
    ref = edge_softmax_ref(logits, jnp.asarray(dst), jnp.asarray(mask), N)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5,
                               atol=2e-6)


def test_edge_softmax_normalizes():
    rng = np.random.default_rng(0)
    E, H, N = 500, 4, 100
    logits = jnp.asarray(rng.normal(size=(E, H)), jnp.float32)
    dst = rng.integers(0, N, size=E).astype(np.int32)
    mask = np.ones(E, bool)
    alpha = np.asarray(edge_softmax_pallas(logits, dst, mask, N, interpret=True))
    sums = np.zeros((N, H))
    np.add.at(sums, dst, alpha)
    present = np.bincount(dst, minlength=N) > 0
    np.testing.assert_allclose(sums[present], 1.0, rtol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.float16])
@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_empty_segment_exact_zeros(dtype, backend):
    """Destinations whose edges are ALL masked out must aggregate to exact
    zeros — not NaN. Regression for the float16 softmax path, where the old
    ``-1e30`` clamp constant overflowed to ``-inf`` and produced
    ``exp(-inf - -inf) * 0 == nan``; also guards the mean's 0/0 case."""
    E, N, F, H = 64, 20, 8, 4
    rng = np.random.default_rng(0)
    dst = rng.integers(0, N // 2, size=E).astype(np.int32)
    dst[:10] = 13  # segment 13 exists but every one of its edges is masked
    mask = np.ones(E, bool)
    mask[:10] = False
    contrib = jnp.asarray(rng.normal(size=(E, F)) * 5, dtype)
    logits = jnp.asarray(rng.normal(size=(E, H)) * 5, dtype)
    dst_a, mask_a = (dst, mask) if backend == "pallas" else (
        jnp.asarray(dst), jnp.asarray(mask)
    )

    mean = np.asarray(
        segment_ops.segment_mean(contrib, dst_a, mask_a, N, backend=backend),
        np.float32,
    )
    assert np.isfinite(mean).all()
    assert not mean[13].any() and not mean[N // 2:].any()

    total = np.asarray(
        segment_ops.segment_sum(contrib, dst_a, mask_a, N, backend=backend),
        np.float32,
    )
    assert np.isfinite(total).all() and not total[13].any()

    if dtype == jnp.float16 and backend == "pallas":
        return  # the packed kernel computes in f32; f16 covers the jnp path
    alpha = np.asarray(
        segment_ops.edge_softmax(logits, dst_a, mask_a, N, backend=backend),
        np.float32,
    )
    assert np.isfinite(alpha).all()
    assert not alpha[:10].any()  # masked edges carry exactly zero weight
    # valid edges still normalize per destination
    sums = np.zeros((N, H))
    np.add.at(sums, dst, alpha)
    present = np.bincount(dst[mask], minlength=N) > 0
    rtol = 2e-5 if dtype == jnp.float32 else 2e-2  # alpha is quantized
    np.testing.assert_allclose(sums[present], 1.0, rtol=rtol)


def test_pack_edges_covers_all_valid():
    rng = np.random.default_rng(1)
    E, N = 777, 130
    dst = rng.integers(0, N, size=E).astype(np.int32)
    mask = rng.random(E) > 0.3
    pack = pack_edges(dst, mask, N, rows=128)
    perm = pack["perm"]
    valid_slots = perm[perm < E]
    assert sorted(valid_slots.tolist()) == sorted(np.flatnonzero(mask).tolist())
    # every packed edge lands in its dst row block
    local = pack["local_dst"].reshape(-1)
    EB = pack["edge_block"]
    for pos in np.flatnonzero(perm < E):
        db = pos // EB
        assert dst[perm[pos]] // 128 == db
        assert dst[perm[pos]] % 128 == local[pos]


if HAVE_HYPOTHESIS:

    @settings(deadline=None, max_examples=15)
    @given(
        E=st.integers(min_value=1, max_value=600),
        F=st.integers(min_value=1, max_value=96),
        N=st.integers(min_value=1, max_value=300),
        seed=st.integers(min_value=0, max_value=100),
    )
    def test_segment_sum_property(E, F, N, seed):
        rng = np.random.default_rng(seed)
        contrib = jnp.asarray(rng.normal(size=(E, F)), jnp.float32)
        dst = rng.integers(0, N, size=E).astype(np.int32)
        mask = rng.random(E) > 0.2
        out = segment_sum_pallas(contrib, dst, mask, N, interpret=True)
        ref = segment_sum_ref(contrib, jnp.asarray(dst), jnp.asarray(mask), N)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize(
    "B,H,KV,D,S,L",
    [(2, 8, 2, 64, 1024, 700), (1, 4, 4, 32, 512, 512), (3, 9, 3, 16, 2048, 1),
     (2, 2, 1, 128, 1024, 999)],  # MQA
)
def test_flash_decode_matches_ref(B, H, KV, D, S, L):
    from repro.kernels.flash_decode.ops import decode_attention_pallas
    from repro.kernels.flash_decode.ref import decode_attention_ref

    rng = np.random.default_rng(B * 100 + H)
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, KV, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, KV, D)), jnp.float32)
    out = decode_attention_pallas(q, k, v, jnp.int32(L), interpret=True)
    ref = decode_attention_ref(q, k, v, jnp.int32(L))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)
