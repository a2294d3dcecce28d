"""Unit + property tests for the hierarchical format encoding (§III-B)."""

import math

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import formats as F
from repro.core.formats import Format, Level
from repro.core.primitives import Prim, clog2
from repro.core.sparsity import Bernoulli, NM, TensorSpec, analyze, analyze_exact


DIMS = {"M": 16, "N": 32}


def test_standard_format_shapes():
    fmts = F.standard_formats(DIMS)
    assert set(fmts) == {"Bitmap", "RLE", "CSR", "COO"}
    for f in fmts.values():
        f.validate(DIMS)


def test_validate_rejects_bad_allocation():
    f = Format.of(Level(Prim.B, "M", 4), Level(Prim.B, "N", 32))
    with pytest.raises(ValueError):
        f.validate(DIMS)   # M covers 4 != 16


def test_csc_matches_paper_example():
    # §III-B: CSC over M×N is UOP(N)-CP(M), UOP at the higher level.
    f = F.csc({"M": 3, "N": 6})
    assert f.levels[0].prim is Prim.UOP and f.levels[0].dim == "N"
    assert f.levels[1].prim is Prim.CP and f.levels[1].dim == "M"


def test_factorizations_cover_and_multiply():
    for parts in (1, 2, 3):
        for fac in F.factorizations(24, parts):
            assert len(fac) == parts
            assert math.prod(fac) == 24


def test_allocate_splits_dims():
    pattern = (Level(Prim.UOP, "N"), Level(Prim.CP, "M"), Level(Prim.CP, "N"))
    allocs = list(F.allocate(pattern, {"M": 3, "N": 6}))
    assert allocs, "expected at least one allocation"
    for fmt in allocs:
        fmt.validate({"M": 3, "N": 6})
        # paper example: N split into subdims (3,2) must be present
    keys = {tuple(int(l.size) for l in fmt.levels) for fmt in allocs}
    assert any(k[0] == 3 and k[2] == 2 for k in keys)


def test_enumerate_patterns_no_leaf_uop():
    pats = list(F.enumerate_patterns(("M", "N"), max_levels=2))
    for p in pats:
        assert p[-1].prim is not Prim.UOP
    # 1-level: 2 dims × 3 prims (no UOP leaf) = 6; 2-level: 4 dim pairs ×
    # (4 prims × 3 prims) = 48 → 54 total.
    assert len(pats) == 54


# ---------------------------------------------------------------------------
# Size analytics: exact vs closed-form on hand-checkable cases
# ---------------------------------------------------------------------------

def test_bitmap_exact_bits():
    dims = {"M": 8, "N": 8}
    mask = np.zeros((8, 8), dtype=bool)
    mask[0, 0] = mask[3, 4] = True
    rep = analyze_exact(F.bitmap(dims), mask, dims, value_bits=16)
    assert rep.metadata_bits == 64          # one bit per element
    assert rep.payload_bits == 2 * 16


def test_csr_exact_bits():
    dims = {"M": 4, "N": 8}
    mask = np.zeros((4, 8), dtype=bool)
    mask[0, :3] = True                      # 3 nnz in row 0
    rep = analyze_exact(F.csr(dims), mask, dims, value_bits=16)
    # UOP: (4+1) pointers × clog2(max_row_nnz+1)=2 bits; CP: 3 × clog2(8)=3
    assert rep.metadata_bits == 5 * 2 + 3 * 3
    assert rep.payload_bits == 3 * 16


def test_hierarchical_bitmap_prunes_empty_groups():
    # Fig. 5 mechanism: an all-zero half costs 1 top bit, not its full bitmap.
    dims = {"M": 4, "N": 8}
    mask = np.zeros((4, 8), dtype=bool)
    mask[:, :4] = True                      # left half dense, right half empty
    flat = analyze_exact(F.bitmap(dims), mask, dims)
    hier = Format.of(Level(Prim.B, "N", 2), Level(Prim.NONE, "M", 4),
                     Level(Prim.B, "N", 4))
    h = analyze_exact(hier, mask, dims)
    assert h.metadata_bits < flat.metadata_bits


def test_expectation_matches_dense_limit():
    spec = TensorSpec({"M": 16, "N": 32}, Bernoulli(1.0))
    rep = analyze(F.bitmap(spec.dims), spec)
    assert rep.payload_bits == spec.dense_bits
    assert rep.metadata_bits == 16 * 32


@settings(max_examples=25, deadline=None)
@given(density=st.floats(0.05, 0.95), seed=st.integers(0, 2**31 - 1))
def test_expectation_matches_monte_carlo_bitmap(density, seed):
    """Law of large numbers: expectation model ≈ exact counts on random masks."""
    dims = {"M": 64, "N": 64}
    rng = np.random.default_rng(seed)
    mask = rng.random((64, 64)) < density
    fmt = F.bitmap(dims)
    exact = analyze_exact(fmt, mask, dims)
    est = analyze(fmt, TensorSpec(dims, Bernoulli(density)))
    assert est.metadata_bits == exact.metadata_bits          # bitmap is exact
    assert est.payload_bits == pytest.approx(exact.payload_bits, rel=0.25)


@settings(max_examples=20, deadline=None)
@given(density=st.floats(0.05, 0.9), seed=st.integers(0, 2**31 - 1))
def test_expectation_matches_monte_carlo_hierarchical(density, seed):
    dims = {"M": 64, "N": 64}
    rng = np.random.default_rng(seed)
    mask = rng.random((64, 64)) < density
    fmt = Format.of(Level(Prim.B, "M", 8), Level(Prim.B, "N", 8),
                    Level(Prim.B, "M", 8), Level(Prim.B, "N", 8))
    exact = analyze_exact(fmt, mask, dims)
    est = analyze(fmt, TensorSpec(dims, Bernoulli(density)))
    assert est.total_bits == pytest.approx(exact.total_bits, rel=0.2)


def test_nm_sparsity_model():
    nm = NM(2, 4)
    assert nm.density == 0.5
    assert nm.prob_nonempty(4) == 1.0
    assert nm.prob_nonempty(1) == pytest.approx(0.5)
    assert nm.prob_nonempty(2) == pytest.approx(1 - 1 / 6)
    assert nm.expected_nnz(8) == 4.0


def test_deeper_format_smaller_payload_at_high_sparsity():
    """Hierarchical formats beat flat bitmap when sparsity is high (Fig. 5)."""
    dims = {"M": 256, "N": 256}
    spec = TensorSpec(dims, Bernoulli(0.05))
    flat = analyze(F.bitmap(dims), spec)
    hier = Format.of(Level(Prim.B, "M", 16), Level(Prim.B, "N", 16),
                     Level(Prim.B, "M", 16), Level(Prim.B, "N", 16))
    h = analyze(hier, spec)
    assert h.metadata_bits < flat.metadata_bits
