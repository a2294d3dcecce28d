"""Property-based equivalence suite for the evaluator planes (PR 4).

Every search plane now scores through one shared cost core, and the whole
PR stack rests on the planes being interchangeable: scalar
:func:`~repro.core.costmodel.evaluate` is a batch of one,
:func:`~repro.core.costmodel.evaluate_batch` materializes rows,
:func:`~repro.core.costmodel.evaluate_batch_gather` gathers (mapping,
format row) index triples over a packed table + fetch tables, and the
``_evaluate_terms`` tail optionally chunks across threads
(``eval_threads``).  These properties pin the contract on RANDOM op
shapes, sparsity levels, and format allocations: all paths are
BIT-identical — every metric and every breakdown term, not just energy.
"""

import dataclasses

import numpy as np
from _hypothesis_compat import given, settings, st

from repro.core import memo
from repro.core.arch import ARCH2, ARCH3
from repro.core.cosearch import CoSearchConfig, cosearch
from repro.core.costmodel import (BatchCost, compile_format, evaluate,
                                  evaluate_batch, evaluate_batch_gather,
                                  format_fetch_table, mapping_ctx,
                                  pack_mappings, resolve_eval_threads)
from repro.core.dataflow import enumerate_mappings
from repro.core.engine import EngineConfig
from repro.core.formats import allocate, enumerate_patterns, standard_formats
from repro.core.sparsity import Bernoulli, TensorSpec
from repro.core.workload import LLMSpec, MatMul, build_llm

_ARCHS = (ARCH2, ARCH3)
_FIELDS = ("energy", "cycles", "edp", "utilization", "dram_bits",
           "e_dram", "e_glb", "e_decode", "dram_cycles", "compute_cycles")


def _assert_batch_equal(a: BatchCost, b: BatchCost) -> None:
    assert len(a) == len(b)
    for f in _FIELDS:
        assert np.array_equal(getattr(a, f), getattr(b, f)), f
    assert a.e_rf == b.e_rf and a.e_mac == b.e_mac


def _format_pool(spec: TensorSpec) -> list:
    """Dense + every named standard format + a spread of allocated 1–2
    level patterns, compiled on ``spec`` — the population random format
    assignments draw from."""
    pool = [compile_format(None, spec)]
    pool += [compile_format(f, spec)
             for f in standard_formats(spec.dims).values()]
    for pat in list(enumerate_patterns(list(spec.dims), max_levels=2))[:8]:
        pool += [compile_format(f, spec)
                 for f in allocate(pat, spec.dims, max_allocs=2)]
    return pool


def _random_case(m, n, k, rho_i, rho_w, sparse_o, arch_idx, seed):
    """One random (op, arch, mappings, format pools, cf_o, assignments)
    evaluation case; returns None when the mapping space is empty."""
    op = MatMul("prop", m, n, k, Bernoulli(rho_i), Bernoulli(rho_w),
                sp_o=Bernoulli(0.5) if sparse_o else Bernoulli(1.0))
    arch = _ARCHS[arch_idx]
    mappings = list(enumerate_mappings(op, arch, spatial_top=2))[:48]
    if not mappings:
        return None
    spec_i = TensorSpec(op.i_dims(), op.sp_i, op.value_bits)
    spec_w = TensorSpec(op.w_dims(), op.sp_w, op.value_bits)
    pool_i = _format_pool(spec_i)
    pool_w = _format_pool(spec_w)
    cf_o = None
    if sparse_o:
        spec_o = TensorSpec(op.o_dims(), op.sp_o, op.value_bits)
        cf_o = compile_format(standard_formats(spec_o.dims)["Bitmap"],
                              spec_o)
    rng = np.random.default_rng(seed)
    i_sel = rng.integers(0, len(pool_i), len(mappings))
    w_sel = rng.integers(0, len(pool_w), len(mappings))
    return op, arch, mappings, pool_i, pool_w, cf_o, i_sel, w_sel, rng


@settings(max_examples=8, deadline=None)
@given(m=st.sampled_from([16, 32, 48, 64, 96]),
       n=st.sampled_from([16, 32, 64, 128]),
       k=st.sampled_from([16, 32, 64, 96]),
       rho_i=st.floats(0.05, 0.95), rho_w=st.floats(0.05, 0.95),
       sparse_o=st.booleans(), arch_idx=st.integers(0, 1),
       seed=st.integers(0, 2**31 - 1))
def test_scalar_vs_batch_all_metrics(m, n, k, rho_i, rho_w, sparse_o,
                                     arch_idx, seed):
    """∀ rows: ``evaluate_batch.report(j)`` == scalar ``evaluate`` of row
    ``j`` — the whole CostReport (energy/cycles/edp/utilization/dram_bits
    AND the breakdown dict), exactly."""
    case = _random_case(m, n, k, rho_i, rho_w, sparse_o, arch_idx, seed)
    if case is None:
        return
    op, arch, mappings, pool_i, pool_w, cf_o, i_sel, w_sel, _ = case
    cf_pairs = [(pool_i[a], pool_w[b]) for a, b in zip(i_sel, w_sel)]
    bc = evaluate_batch(op, arch, mappings, cf_pairs, cf_o)
    for j, (mapping, (cf_i, cf_w)) in enumerate(zip(mappings, cf_pairs)):
        assert bc.report(j) == evaluate(op, arch, mapping, cf_i, cf_w, cf_o)


@settings(max_examples=8, deadline=None)
@given(m=st.sampled_from([16, 32, 48, 64, 96]),
       n=st.sampled_from([16, 32, 64, 128]),
       k=st.sampled_from([16, 32, 64, 96]),
       rho_i=st.floats(0.05, 0.95), rho_w=st.floats(0.05, 0.95),
       sparse_o=st.booleans(), arch_idx=st.integers(0, 1),
       seed=st.integers(0, 2**31 - 1))
def test_batch_vs_gather_bit_identical(m, n, k, rho_i, rho_w, sparse_o,
                                       arch_idx, seed):
    """``evaluate_batch_gather`` over random (mapping, I-format, W-format)
    index triples == ``evaluate_batch`` on the materialized rows — every
    metric array, bit for bit."""
    case = _random_case(m, n, k, rho_i, rho_w, sparse_o, arch_idx, seed)
    if case is None:
        return
    op, arch, mappings, pool_i, pool_w, cf_o, _, _, rng = case
    rows = 3 * len(mappings)
    map_idx = rng.integers(0, len(mappings), rows)
    i_idx = rng.integers(0, len(pool_i), rows)
    w_idx = rng.integers(0, len(pool_w), rows)
    want = evaluate_batch(op, arch, [mappings[x] for x in map_idx],
                          [(pool_i[a], pool_w[b])
                           for a, b in zip(i_idx, w_idx)], cf_o)
    table = pack_mappings(mappings)
    got = evaluate_batch_gather(op, arch, table,
                                format_fetch_table(pool_i, table), i_idx,
                                format_fetch_table(pool_w, table), w_idx,
                                map_idx, cf_o)
    _assert_batch_equal(want, got)
    # a precomputed ctx (the sweep/co-search reuse path) changes nothing
    ctx = mapping_ctx(op, arch, table, cf_o)
    got_ctx = evaluate_batch_gather(op, arch, table,
                                    format_fetch_table(pool_i, table),
                                    i_idx,
                                    format_fetch_table(pool_w, table),
                                    w_idx, map_idx, cf_o, ctx=ctx)
    _assert_batch_equal(want, got_ctx)


@settings(max_examples=6, deadline=None)
@given(m=st.sampled_from([32, 64, 96]), n=st.sampled_from([32, 64, 128]),
       k=st.sampled_from([32, 64]),
       rho_i=st.floats(0.05, 0.95), rho_w=st.floats(0.05, 0.95),
       threads=st.integers(2, 7), arch_idx=st.integers(0, 1),
       seed=st.integers(0, 2**31 - 1))
def test_eval_threads_bit_identical(m, n, k, rho_i, rho_w, threads,
                                    arch_idx, seed):
    """``eval_threads=1`` vs ``eval_threads=N`` (and auto): the chunked
    ``_evaluate_terms`` tail concatenates to the identical arrays — the
    tail is elementwise per candidate row, so any chunking is exact."""
    case = _random_case(m, n, k, rho_i, rho_w, False, arch_idx, seed)
    if case is None:
        return
    op, arch, mappings, pool_i, pool_w, cf_o, _, _, rng = case
    # enough rows that every thread gets several chunks' worth of work
    rows = 50 * len(mappings)
    map_idx = rng.integers(0, len(mappings), rows)
    i_idx = rng.integers(0, len(pool_i), rows)
    w_idx = rng.integers(0, len(pool_w), rows)
    table = pack_mappings(mappings)
    ft_i = format_fetch_table(pool_i, table)
    ft_w = format_fetch_table(pool_w, table)
    serial = evaluate_batch_gather(op, arch, table, ft_i, i_idx, ft_w,
                                   w_idx, map_idx, cf_o, eval_threads=1)
    for t in (threads, None):
        chunked = evaluate_batch_gather(op, arch, table, ft_i, i_idx,
                                        ft_w, w_idx, map_idx, cf_o,
                                        eval_threads=t)
        _assert_batch_equal(serial, chunked)


def test_resolve_eval_threads_policy():
    """Explicit counts win (floored at 1); auto stays serial below the
    chunk threshold so small batches never pay pool overhead."""
    assert resolve_eval_threads(4, 10) == 4
    assert resolve_eval_threads(0, 10) == 1
    assert resolve_eval_threads(None, 100) == 1
    assert resolve_eval_threads(None, 10_000_000) >= 1


def test_cosearch_planes_bit_identical():
    """End-to-end on the co-search driver: the seed scalar loop
    (use_batch=False), the PR-3 repack plane (use_gather=False), the
    gather plane, and the gather plane with a forced thread count all
    produce the identical design, metric, and evaluation count."""
    fast = CoSearchConfig(engine=EngineConfig(max_levels=2,
                                              max_allocs_per_pattern=16),
                          spatial_top=2, max_pairs=6)
    wl = build_llm(LLMSpec("eq-test", 1, 128, 256, 4), seq=64,
                   act_density=0.4, w_density=0.25)

    def fingerprint(res):
        return (res.design.pattern_i, res.design.pattern_w,
                res.design.energy, res.design.cycles, res.evaluations,
                tuple((str(o.mapping), str(o.fmt_i), str(o.fmt_w))
                      for o in res.design.ops))

    with memo.disabled():
        fps = [fingerprint(cosearch(wl, ARCH3, cfg)) for cfg in (
            dataclasses.replace(fast, use_batch=False),
            dataclasses.replace(fast, use_gather=False),
            fast,
            dataclasses.replace(fast, eval_threads=3),
        )]
    assert fps[0] == fps[1] == fps[2] == fps[3]
