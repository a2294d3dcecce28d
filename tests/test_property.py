"""Hypothesis property tests on system invariants.

These pin down the physics of the modeling plane: monotonicity in density,
conservation between exact and expected analyses, legality of every design
the searches emit, and idempotence/determinism guarantees the distributed
runtime depends on.
"""

import math

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import formats as F
from repro.core.arch import ARCH2, ARCH3
from repro.core.costmodel import compile_format, dense_format, evaluate
from repro.core.dataflow import enumerate_mappings, tile_fits
from repro.core.engine import EngineConfig, generate_candidates
from repro.core.sparsity import Bernoulli, TensorSpec, analyze
from repro.core.workload import MatMul


@settings(max_examples=20, deadline=None)
@given(rho=st.floats(0.02, 0.98))
def test_compressed_size_monotone_in_density(rho):
    """More non-zeros can never make the SAME format smaller."""
    dims = {"M": 256, "N": 512}
    lo = analyze(F.bitmap(dims), TensorSpec(dims, Bernoulli(rho)))
    hi = analyze(F.bitmap(dims), TensorSpec(dims, Bernoulli(min(rho + 0.01, 1.0))))
    assert hi.total_bits >= lo.total_bits - 1e-6


@settings(max_examples=20, deadline=None)
@given(rho=st.floats(0.05, 0.95), seed=st.integers(0, 2**31 - 1))
def test_csr_exact_vs_expected(rho, seed):
    """Expectation model tracks exact counts for CSR on random masks."""
    dims = {"M": 96, "N": 128}
    rng = np.random.default_rng(seed)
    mask = rng.random((96, 128)) < rho
    from repro.core.sparsity import analyze_exact
    exact = analyze_exact(F.csr(dims), mask, dims)
    est = analyze(F.csr(dims), TensorSpec(dims, Bernoulli(rho)))
    assert est.total_bits == pytest.approx(exact.total_bits, rel=0.25)


@settings(max_examples=10, deadline=None)
@given(rho_i=st.floats(0.1, 1.0), rho_w=st.floats(0.1, 1.0))
def test_energy_monotone_in_density(rho_i, rho_w):
    """Denser operands cost at least as much energy (same mapping/format)."""
    op_lo = MatMul("p", 128, 256, 128, Bernoulli(rho_i * 0.9),
                   Bernoulli(rho_w * 0.9))
    op_hi = MatMul("p", 128, 256, 128, Bernoulli(rho_i), Bernoulli(rho_w))
    m = next(iter(enumerate_mappings(op_hi, ARCH3)))

    def cost(op):
        cf_i = compile_format(F.bitmap(op.i_dims()),
                              TensorSpec(op.i_dims(), op.sp_i))
        cf_w = compile_format(F.bitmap(op.w_dims()),
                              TensorSpec(op.w_dims(), op.sp_w))
        return evaluate(op, ARCH3, m, cf_i, cf_w).energy

    assert cost(op_hi) >= cost(op_lo) * 0.999


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 1000))
def test_every_enumerated_mapping_is_legal(seed):
    rng = np.random.default_rng(seed)
    m_, n_, k_ = (int(rng.choice([64, 128, 256, 384])) for _ in range(3))
    op = MatMul("r", m_, n_, k_)
    for mapping in enumerate_mappings(op, ARCH2, spatial_top=2):
        sp = mapping.spatial
        assert sp["M"] * sp["N"] * sp["K"] <= ARCH2.macs
        assert tile_fits(op, mapping.tile, ARCH2)
        for d in ("M", "N", "K"):
            assert mapping.tile[d] >= 1


@settings(max_examples=8, deadline=None)
@given(rho=st.floats(0.03, 0.5))
def test_candidates_never_worse_than_dense(rho):
    """Every surviving candidate compresses (EqData < dense bits)."""
    spec = TensorSpec({"M": 512, "N": 512}, Bernoulli(rho))
    cands = generate_candidates(spec, EngineConfig(max_levels=2,
                                                   max_allocs_per_pattern=16))
    assert cands
    for c in cands[:4]:
        assert c.report.total_bits < spec.dense_bits


@settings(max_examples=10, deadline=None)
@given(step=st.integers(0, 10_000), seed=st.integers(0, 2**31 - 1))
def test_pipeline_pure_function_of_step(step, seed):
    from repro.data.pipeline import TokenPipeline
    p = TokenPipeline(vocab=997, seq_len=8, global_batch=2, seed=seed)
    a = p.batch_at(step)
    b = p.batch_at(step)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    np.testing.assert_array_equal(a["labels"], b["labels"])


@settings(max_examples=10, deadline=None)
@given(rho=st.floats(0.05, 0.6), bn=st.sampled_from([8, 16, 32]))
def test_bitmap_compression_roundtrip_property(rho, bn):
    """compress → kernel-format metadata is self-consistent: counts sum to
    blocks, row ids are in range, reconstruction matches the mask."""
    from repro.kernels import ref
    rng = np.random.default_rng(int(rho * 1e6) + bn)
    n = k = 128
    gn, gk = n // bn, k // bn
    bitmap = rng.random((gn, gk)) < rho
    w = rng.normal(size=(n, k)).astype(np.float32)
    w *= np.repeat(np.repeat(bitmap, bn, 0), bn, 1)
    blocks, counts, row_ids, offsets, bm = ref.compress_bitmap_host(w, bn, bn)
    assert counts.sum() == bitmap.sum()
    assert (row_ids[: max(counts.sum(), 1)] < gn).all()
    assert (bm == bitmap).all()
