"""Pallas kernel tests: shape/dtype sweeps + hypothesis properties, always
against the ref.py pure-jnp oracles (interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels import ops, ref


def _block_sparse_w(rng, n, k, bn, bk, density):
    gn, gk = n // bn, k // bk
    bitmap = rng.random((gn, gk)) < density
    w = rng.normal(size=(n, k)).astype(np.float32)
    mask = np.repeat(np.repeat(bitmap, bn, 0), bk, 1)
    return (w * mask).astype(np.float32), bitmap


# ---------------------------------------------------------------------------
# bitmap_spmm
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,n,k,bn,bk", [
    (16, 32, 32, 8, 8),
    (32, 64, 32, 16, 16),
    (8, 128, 256, 32, 64),
    (128, 128, 128, 128, 128),     # single block
])
@pytest.mark.parametrize("density", [0.0, 0.3, 1.0])
def test_bitmap_spmm_shapes(m, n, k, bn, bk, density):
    rng = np.random.default_rng(m * 1000 + n + k)
    w, bitmap = _block_sparse_w(rng, n, k, bn, bk, density)
    x = rng.normal(size=(m, n)).astype(np.float32)
    comp = ops.compress_bitmap(w, bn, bk)
    got = ops.bitmap_spmm(jnp.asarray(x), comp, bm=min(128, m))
    want = ref.bitmap_spmm_ref(jnp.asarray(x), jnp.asarray(w),
                               jnp.asarray(bitmap), bn, bk)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_bitmap_spmm_dtypes(dtype):
    rng = np.random.default_rng(7)
    w, bitmap = _block_sparse_w(rng, 64, 64, 16, 16, 0.4)
    x = rng.normal(size=(32, 64)).astype(np.float32)
    comp = ops.compress_bitmap(w.astype(dtype), 16, 16)
    got = ops.bitmap_spmm(jnp.asarray(x, dtype), comp, bm=32)
    want = ref.bitmap_spmm_ref(jnp.asarray(x, dtype),
                               jnp.asarray(w, dtype),
                               jnp.asarray(bitmap), 16, 16)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2, atol=2e-2)


@settings(max_examples=12, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), density=st.floats(0.0, 1.0))
def test_bitmap_spmm_property(seed, density):
    """∀ random block patterns: kernel ≡ dense-masked matmul."""
    rng = np.random.default_rng(seed)
    w, bitmap = _block_sparse_w(rng, 64, 32, 16, 8, density)
    x = rng.normal(size=(16, 64)).astype(np.float32)
    comp = ops.compress_bitmap(w, 16, 8)
    got = ops.bitmap_spmm(jnp.asarray(x), comp, bm=16)
    want = jnp.dot(jnp.asarray(x), jnp.asarray(w))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_bitmap_compression_ratio_tracks_density():
    rng = np.random.default_rng(3)
    w_sparse, _ = _block_sparse_w(rng, 256, 256, 32, 32, 0.25)
    w_dense, _ = _block_sparse_w(rng, 256, 256, 32, 32, 1.0)
    r_s = ops.compress_bitmap(w_sparse, 32, 32).compression_ratio
    r_d = ops.compress_bitmap(w_dense, 32, 32).compression_ratio
    assert r_s < 0.5 and r_d >= 1.0


# ---------------------------------------------------------------------------
# nm_spmm (2:4)
# ---------------------------------------------------------------------------

def _nm_sparse_w(rng, n, k):
    w = rng.normal(size=(n, k)).astype(np.float32)
    # prune to exact 2:4 along N
    wg = w.reshape(n // 4, 4, k)
    order = np.argsort(-np.abs(wg), axis=1)
    mask = np.zeros_like(wg, dtype=bool)
    np.put_along_axis(mask, order[:, :2, :], True, axis=1)
    return (wg * mask).reshape(n, k).astype(np.float32)


@pytest.mark.parametrize("m,n,k", [
    (16, 32, 32), (32, 64, 128), (8, 256, 64), (128, 128, 128),
])
def test_nm_spmm_shapes(m, n, k):
    rng = np.random.default_rng(n + k)
    w = _nm_sparse_w(rng, n, k)
    x = rng.normal(size=(m, n)).astype(np.float32)
    comp = ops.compress_nm(w)
    got = ops.nm_spmm(jnp.asarray(x), comp, bm=min(128, m),
                      bn=min(128, n), bk=min(128, k))
    want = jnp.dot(jnp.asarray(x), jnp.asarray(w))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_nm_expand_roundtrip():
    rng = np.random.default_rng(11)
    w = _nm_sparse_w(rng, 64, 16)
    comp = ops.compress_nm(w)
    dense = ref.nm_expand_ref(comp.values, comp.indices)
    np.testing.assert_allclose(np.asarray(dense), w, rtol=1e-6, atol=1e-6)


@settings(max_examples=12, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_nm_spmm_property(seed):
    rng = np.random.default_rng(seed)
    w = _nm_sparse_w(rng, 32, 16)
    x = rng.normal(size=(8, 32)).astype(np.float32)
    comp = ops.compress_nm(w)
    got = ops.nm_spmm(jnp.asarray(x), comp, bm=8, bn=32, bk=16)
    want = jnp.dot(jnp.asarray(x), jnp.asarray(w))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_nm_compression_ratio():
    assert ops.NMCompressed(jnp.zeros((2, 2)), jnp.zeros((2, 2), jnp.int8),
                            4, 2).compression_ratio < 0.6


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bh,sq,skv,d,bq,bk", [
    (2, 64, 64, 32, 16, 16),
    (4, 128, 128, 64, 32, 64),
    (1, 32, 32, 128, 32, 32),      # single tile
    (3, 96, 96, 16, 32, 32),
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_shapes(bh, sq, skv, d, bq, bk, causal):
    rng = np.random.default_rng(sq + d)
    q = jnp.asarray(rng.normal(size=(bh, sq, d)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(bh, skv, d)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(bh, skv, d)).astype(np.float32))
    got = ops.flash_attention(q, k, v, causal=causal, bq=bq, bk=bk)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_flash_attention_property(seed):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(2, 64, 32)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(2, 64, 32)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(2, 64, 32)).astype(np.float32))
    got = ops.flash_attention(q, k, v, causal=True, bq=16, bk=32)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_flash_attention_bf16():
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(2, 64, 32)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(2, 64, 32)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(2, 64, 32)), jnp.bfloat16)
    got = ops.flash_attention(q, k, v, causal=True, bq=32, bk=32)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=5e-2, atol=5e-2)


# ---------------------------------------------------------------------------
# row padding, tile rules, backend guard
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("pipeline", [True, False])
@pytest.mark.parametrize("m", [1, 3, 37])
def test_bitmap_spmm_padded_rows_match_unpadded(m, pipeline):
    """The wrapper pads m bf16 rows to a 16-row multiple and slices the
    padding off: the result equals the raw kernel run on the m rows."""
    from repro.kernels.bitmap_spmm import bitmap_spmm_pallas
    rng = np.random.default_rng(m)
    w, _ = _block_sparse_w(rng, 64, 64, 16, 16, 0.5)
    x = jnp.asarray(rng.normal(size=(m, 64)), jnp.bfloat16)
    comp = ops.compress_bitmap(w, 16, 16)
    got = ops.bitmap_spmm(x, comp, pipeline=pipeline)
    want = bitmap_spmm_pallas(x, comp.blocks, comp.counts, comp.row_ids,
                              comp.offsets, k=64, bm=m,
                              t_max=comp.max_per_col, interpret=True,
                              pipeline=pipeline)
    assert got.shape == (m, 64)
    # a 1-row matmul takes XLA:CPU's matrix-vector path: same f32 sums in
    # another order, so allow f32 rounding over the 64-term contraction
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("pipeline", [True, False])
@pytest.mark.parametrize("m", [1, 3, 37])
def test_nm_spmm_padded_rows_match_unpadded(m, pipeline):
    from repro.kernels.nm_spmm import nm_spmm_pallas
    rng = np.random.default_rng(m)
    w = _nm_sparse_w(rng, 64, 32)
    x = jnp.asarray(rng.normal(size=(m, 64)), jnp.bfloat16)
    comp = ops.compress_nm(w)
    got = ops.nm_spmm(x, comp, pipeline=pipeline)
    want = nm_spmm_pallas(x, comp.values, comp.indices, bm=m, bn=64, bk=32,
                          interpret=True, pipeline=pipeline)
    assert got.shape == (m, 32)
    # a 1-row matmul takes XLA:CPU's matrix-vector path: same f32 sums in
    # another order, so allow f32 rounding over the 64-term contraction
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_row_and_lane_tiles_follow_mosaic_tiling():
    from repro.kernels import tiling
    # fewest ≤128-row tiles, each a multiple of the sublane packing
    bf16 = {m: tiling.row_tile(m, 2) for m in (1, 3, 8, 37, 130, 200, 512)}
    assert bf16 == {1: 16, 3: 16, 8: 16, 37: 48, 130: 80, 200: 112,
                    512: 128}
    assert tiling.row_tile(3, 4) == 8 and tiling.row_tile(3, 1) == 32
    # lane blocks: 128-multiples that divide, else the whole axis
    assert tiling.lane_divisors(13696) == [128, 13696]
    assert tiling.lane_divisors(512) == [128, 256, 512]
    assert tiling.lane_divisors(64) == [64]
    assert tiling.lane_tile(4096) == 128 and tiling.lane_tile(64) == 64


def test_interpret_mode_only_on_cpu(monkeypatch):
    """Interpret mode is the CPU's; TPU compiles; anything else raises
    instead of quietly interpreting."""
    for backend, interpret in (("cpu", True), ("tpu", False)):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        assert ops._interpret() is interpret
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu'"):
        ops._interpret()
