"""Batch evaluator + memoization correctness (the search hot path).

Pins the two contracts the vectorized rewrite must keep:
  * ``evaluate_batch`` over a heterogeneous candidate set reproduces the
    scalar ``evaluate`` report for every candidate (1e-9 relative);
  * every cache (compile_format / analyze / mappings / candidates /
    _search_op) is semantically invisible — co-search results are identical
    with caching off, cold, and warm.
"""

import dataclasses
import math
import types

import pytest
from _hypothesis_compat import given, settings, st

import numpy as np

from repro.core import formats as F
from repro.core import memo
from repro.core.arch import ARCH1, ARCH2, ARCH3
from repro.core.cosearch import (CoSearchConfig, _dense_sentinel, _pair_rank,
                                 cosearch)
from repro.core.costmodel import (compile_format, dense_format, evaluate,
                                  evaluate_batch)
from repro.core.dataflow import enumerate_mappings, mappings_for
from repro.core.engine import EngineConfig
from repro.core.formats import Format, Level
from repro.core.primitives import Prim
from repro.core.sparsity import NM, Bernoulli, TensorSpec
from repro.core.workload import LLMSpec, MatMul, build_llm

OPS = (
    MatMul("mid", 128, 256, 128, Bernoulli(0.5), Bernoulli(0.25),
           Bernoulli(0.3)),
    MatMul("nm", 64, 512, 256, Bernoulli(0.9), NM(2, 4), Bernoulli(0.8),
           count=3.0),
    MatMul("decode", 1, 1024, 512, Bernoulli(0.2), Bernoulli(0.15)),
)
ARCHS = (ARCH1, ARCH2, ARCH3)


def _i_formats(op):
    spec = TensorSpec(op.i_dims(), op.sp_i, op.value_bits)
    n1 = 16 if op.N % 256 == 0 else 8
    hier = Format.of(Level(Prim.B, "N", n1), Level(Prim.NONE, "M", op.M),
                     Level(Prim.B, "N", op.N // n1))
    return [dense_format(spec),
            compile_format(F.bitmap(op.i_dims()), spec),
            compile_format(F.rle(op.i_dims()), spec),
            compile_format(hier, spec)]


def _w_formats(op):
    spec = TensorSpec(op.w_dims(), op.sp_w, op.value_bits)
    return [dense_format(spec),
            compile_format(F.bitmap(op.w_dims()), spec),
            compile_format(F.csr(op.w_dims()), spec),
            compile_format(F.coo(op.w_dims()), spec)]


def _assert_reports_close(got, want, rel=1e-9):
    for f in ("energy", "cycles", "edp", "utilization", "dram_bits"):
        assert math.isclose(getattr(got, f), getattr(want, f),
                            rel_tol=rel, abs_tol=1e-12), f
    assert set(got.breakdown) == set(want.breakdown)
    for k, v in want.breakdown.items():
        assert math.isclose(got.breakdown[k], v,
                            rel_tol=rel, abs_tol=1e-12), k


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_batch_matches_scalar_evaluate(seed):
    """∀ random (op, arch, mapping, format-pair) sets: one evaluate_batch
    call == per-candidate scalar evaluate, on every CostReport field."""
    rng = np.random.default_rng(seed)
    op = OPS[rng.integers(len(OPS))]
    arch = ARCHS[rng.integers(len(ARCHS))]
    cfs_i, cfs_w = _i_formats(op), _w_formats(op)
    all_mappings = list(enumerate_mappings(op, arch, spatial_top=2))
    take = rng.choice(len(all_mappings), size=min(40, len(all_mappings)),
                      replace=False)
    mappings = [all_mappings[i] for i in take]
    pairs = [(cfs_i[rng.integers(len(cfs_i))], cfs_w[rng.integers(len(cfs_w))])
             for _ in mappings]
    cf_o = None
    if rng.random() < 0.5 and op.sp_o.density < 1.0:
        cf_o = compile_format(F.bitmap(op.o_dims()),
                              TensorSpec(op.o_dims(), op.sp_o, op.value_bits))
    bc = evaluate_batch(op, arch, mappings, pairs, cf_o)
    assert len(bc) == len(mappings)
    for j, (mapping, (cf_i, cf_w)) in enumerate(zip(mappings, pairs)):
        _assert_reports_close(bc.report(j),
                              evaluate(op, arch, mapping, cf_i, cf_w, cf_o))


def test_batch_broadcasts_single_pair():
    op, arch = OPS[0], ARCH3
    cf_i, cf_w = _i_formats(op)[1], _w_formats(op)[1]
    mappings = list(enumerate_mappings(op, arch, spatial_top=2))[:10]
    bc = evaluate_batch(op, arch, mappings, [(cf_i, cf_w)])
    for j, m in enumerate(mappings):
        _assert_reports_close(bc.report(j), evaluate(op, arch, m, cf_i, cf_w))


def test_batch_rejects_misaligned_pairs():
    op, arch = OPS[0], ARCH3
    cf_i, cf_w = _i_formats(op)[0], _w_formats(op)[0]
    mappings = list(enumerate_mappings(op, arch, spatial_top=2))[:3]
    with pytest.raises(ValueError):
        evaluate_batch(op, arch, mappings, [(cf_i, cf_w)] * 2)


# ---------------------------------------------------------------------------
# Caching
# ---------------------------------------------------------------------------

_WL = build_llm(LLMSpec("cachetest", 2, 256, 1024, 4), seq=128,
                decode_tokens=8, act_density=0.4, w_density=0.25)
_CFG = CoSearchConfig(engine=EngineConfig(max_levels=2,
                                          max_allocs_per_pattern=16),
                      spatial_top=2, max_pairs=6)


def _design_fingerprint(res):
    return (res.design.pattern_i, res.design.pattern_w, res.design.energy,
            res.design.cycles, res.evaluations,
            tuple((str(o.mapping), str(o.fmt_i), str(o.fmt_w))
                  for o in res.design.ops))


def test_cosearch_unchanged_with_caching_on_off():
    """Caches must be semantically invisible: identical designs, metrics and
    evaluation counts with caching disabled, cold, and warm."""
    with memo.disabled():
        off = _design_fingerprint(cosearch(_WL, ARCH3, _CFG))
    memo.clear()
    cold = _design_fingerprint(cosearch(_WL, ARCH3, _CFG))
    warm = _design_fingerprint(cosearch(_WL, ARCH3, _CFG))
    assert cold == off
    assert warm == off


def test_scalar_path_matches_batch_path():
    """use_batch=False (legacy scalar loop) picks the same design."""
    wl = build_llm(LLMSpec("scalartest", 1, 128, 256, 4), seq=64,
                   act_density=0.4, w_density=0.25)
    cfg = CoSearchConfig(engine=_CFG.engine, spatial_top=2, max_pairs=4)
    scalar_cfg = dataclasses.replace(cfg, use_batch=False)
    with memo.disabled():
        a = _design_fingerprint(cosearch(wl, ARCH3, scalar_cfg))
        b = _design_fingerprint(cosearch(wl, ARCH3, cfg))
    assert a == b


def test_mappings_for_matches_enumerate_and_caches():
    op, arch = OPS[0], ARCH2
    want = tuple(enumerate_mappings(op, arch, 0.5, 0.25, spatial_top=2))
    got = mappings_for(op, arch, 0.5, 0.25, spatial_top=2)
    assert got == want
    assert mappings_for(op, arch, 0.5, 0.25, spatial_top=2) is got  # cached
    renamed = MatMul("other-name", op.M, op.N, op.K, op.sp_i, op.sp_w)
    assert mappings_for(renamed, arch, 0.5, 0.25, spatial_top=2) is got


# ---------------------------------------------------------------------------
# Pair-ranking sentinel (inf/4 fix)
# ---------------------------------------------------------------------------

def test_dense_sentinel_is_finite_and_orders_pairs():
    c = lambda e: types.SimpleNamespace(eq_data=e)
    ca, cb = c(100.0), c(300.0)
    sentinel = _dense_sentinel([ca, cb, None])
    assert math.isfinite(sentinel) and sentinel > cb.eq_data
    # part-dense pairs order by their compressed side's EqData...
    assert _pair_rank((None, ca), sentinel) < _pair_rank((None, cb), sentinel)
    assert _pair_rank((ca, None), sentinel) < _pair_rank((cb, None), sentinel)
    # ...and the fully-dense pair ranks after every part-dense pair
    assert _pair_rank((None, None), sentinel) > _pair_rank((None, cb), sentinel)
    # no candidates at all still yields a finite sentinel
    assert math.isfinite(_dense_sentinel([None, None]))
