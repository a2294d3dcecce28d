"""Compression-aware memory pipeline tests.

Contracts pinned here:

  * the double-buffered streaming kernels (``pipeline=True``, the dispatch
    default) are BIT-identical to the naive grid-walk kernels at fp32 —
    same accumulation order, same dot widening — across densities
    including fully-empty and fully-dense weights;
  * ``repro.kernels.ops.pipeline_default`` swaps what ``pipeline=None``
    resolves to, and restores on exit;
  * the cost model's reuse term (``HardwareConfig.glb_resident_frac``)
    changes NOTHING at frac 0, moves refetch traffic DRAM→GLB at frac > 0
    with exact bit conservation, and is bit-identical across all four
    evaluator planes (scalar / batch / gather / threaded);
  * ``with_streaming_reuse`` names round-trip through ``arch_by_name``;
  * ``instrument()`` splits W traffic into distinct vs streamed bits with
    ``M / tile_M`` passes, and the per-level calibration fit
    (``fit_glb_scale`` / ``calibrated_hardware``) recovers a planted GLB
    coefficient from the refetch residual;
  * durable memo snapshots (``memo.save`` / ``memo.load``) replay across a
    clear and reject stale code fingerprints without touching caches;
  * ``CoSearchConfig.op_workers`` is bit-identical to the serial per-op
    loop (designs AND SearchStats) and normalized out of the search cache
    key;
  * scanned and unrolled serving share jitted-kernel cache entries even on
    a store whose layers realize very different sparsity;
  * ``StackedStore.padding_overhead`` accounts a dense-layer outlier
    exactly, and the padded scanned forward still decodes bit-identically
    to the per-layer dispatch.
"""

import dataclasses
import pickle

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import exec as rexec
from repro.configs import get_config
from repro.core import memo
from repro.core.arch import ARCH3, arch_by_name, with_streaming_reuse
from repro.core.cosearch import (CoSearchConfig, _search_op_key, cosearch)
from repro.core.costmodel import compile_format, evaluate_batch
from repro.core.dataflow import enumerate_mappings, irrelevant_refetch
from repro.core.engine import EngineConfig
from repro.core.formats import standard_formats
from repro.core.sparsity import Bernoulli, BlockBernoulli, TensorSpec
from repro.core.workload import LLMSpec, MatMul, build_llm
from repro.exec.calibrate import (CalibRow, calibrated_hardware,
                                  fit_glb_scale)
from repro.exec.compress import _role_path, stack_store
from repro.kernels import ops as kops
from repro.models import attention as attn_mod
from repro.models import layers as L
from repro.models.transformer import Model

FAST = CoSearchConfig(objective="edp",
                      engine=EngineConfig(max_levels=2,
                                          max_allocs_per_pattern=16),
                      spatial_top=2, max_pairs=6)


@pytest.fixture()
def fp32_compute(monkeypatch):
    monkeypatch.setattr(L, "COMPUTE_DTYPE", jnp.float32)
    monkeypatch.setattr(attn_mod, "COMPUTE_DTYPE", jnp.float32)


def _block_sparse_w(rng, n, k, bn, bk, density):
    gn, gk = n // bn, k // bk
    bitmap = rng.random((gn, gk)) < density
    w = rng.normal(size=(n, k)).astype(np.float32)
    mask = np.repeat(np.repeat(bitmap, bn, 0), bk, 1)
    return (w * mask).astype(np.float32)


def _nm_sparse_w(rng, n, k):
    wg = rng.normal(size=(n // 4, 4, k)).astype(np.float32)
    order = np.argsort(-np.abs(wg), axis=1)
    mask = np.zeros_like(wg, dtype=bool)
    np.put_along_axis(mask, order[:, :2, :], True, axis=1)
    return (wg * mask).reshape(n, k).astype(np.float32)


# ---------------------------------------------------------------------------
# pipelined kernels ≡ naive kernels
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,n,k,bn,bk", [
    (16, 32, 32, 8, 8),
    (32, 64, 32, 16, 16),
    (8, 128, 256, 32, 64),
    (128, 128, 128, 128, 128),     # single block
])
@pytest.mark.parametrize("density", [0.0, 0.5, 1.0])
def test_bitmap_pipelined_bit_identical(m, n, k, bn, bk, density):
    """Same per-``kj`` block walk, same widened fp32 dot: the streaming
    kernel's output must equal the naive kernel's BIT for bit, including
    the all-empty and all-dense extremes."""
    rng = np.random.default_rng(m + n + k)
    w = _block_sparse_w(rng, n, k, bn, bk, density)
    x = jnp.asarray(rng.normal(size=(m, n)).astype(np.float32))
    comp = kops.compress_bitmap(w, bn, bk)
    y_pipe = kops.bitmap_spmm(x, comp, bm=min(128, m), pipeline=True)
    y_naive = kops.bitmap_spmm(x, comp, bm=min(128, m), pipeline=False)
    assert np.array_equal(np.asarray(y_pipe), np.asarray(y_naive))


@pytest.mark.parametrize("m,n,k", [
    (16, 32, 32), (32, 64, 128), (8, 256, 64), (128, 128, 128),
])
def test_nm_pipelined_bit_identical(m, n, k):
    rng = np.random.default_rng(n + k)
    w = _nm_sparse_w(rng, n, k)
    x = jnp.asarray(rng.normal(size=(m, n)).astype(np.float32))
    comp = kops.compress_nm(w)
    kw = dict(bm=min(128, m), bn=min(128, n), bk=min(128, k))
    y_pipe = kops.nm_spmm(x, comp, pipeline=True, **kw)
    y_naive = kops.nm_spmm(x, comp, pipeline=False, **kw)
    # acceptance bound is ≤ 1e-6; the shared decode + stripe order makes
    # it exact in practice
    assert np.array_equal(np.asarray(y_pipe), np.asarray(y_naive))


def test_pipeline_default_override():
    assert kops.resolve_pipeline(None) is True
    assert kops.resolve_pipeline(False) is False
    with kops.pipeline_default(False):
        assert kops.resolve_pipeline(None) is False
        assert kops.resolve_pipeline(True) is True
    assert kops.resolve_pipeline(None) is True


# ---------------------------------------------------------------------------
# reuse-aware cache term
# ---------------------------------------------------------------------------

def _eval_case(arch):
    op = MatMul("reuse", 64, 128, 96, Bernoulli(0.6), Bernoulli(0.3))
    mappings = list(enumerate_mappings(op, arch, spatial_top=2))[:32]
    spec_i = TensorSpec(op.i_dims(), op.sp_i, op.value_bits)
    spec_w = TensorSpec(op.w_dims(), op.sp_w, op.value_bits)
    cf_i = compile_format(standard_formats(spec_i.dims)["Bitmap"], spec_i)
    cf_w = compile_format(standard_formats(spec_w.dims)["RLE"], spec_w)
    return op, mappings, [(cf_i, cf_w)] * len(mappings)


def test_reuse_term_zero_frac_is_identity():
    """frac = 0 keeps every metric bit-identical to the base arch — the
    term is guarded, not just numerically small."""
    op, mappings, pairs = _eval_case(ARCH3)
    base = evaluate_batch(op, ARCH3, mappings, pairs)
    zero = evaluate_batch(op, with_streaming_reuse(ARCH3, 0.0), mappings,
                          pairs)
    for f in ("energy", "cycles", "edp", "dram_bits", "e_dram", "e_glb"):
        assert np.array_equal(getattr(base, f), getattr(zero, f)), f


def test_reuse_term_moves_refetch_dram_to_glb():
    """frac > 0 only ever lowers DRAM traffic, adds the same bits to GLB
    (conservation), and never increases total energy (GLB pJ/bit < DRAM
    pJ/bit on every shipped arch)."""
    arch = with_streaming_reuse(ARCH3, 0.75)
    op, mappings, pairs = _eval_case(ARCH3)
    base = evaluate_batch(op, ARCH3, mappings, pairs)
    reuse = evaluate_batch(op, arch, mappings, pairs)
    assert np.all(reuse.dram_bits <= base.dram_bits)
    assert np.any(reuse.dram_bits < base.dram_bits)
    assert np.all(reuse.e_dram <= base.e_dram)
    assert np.all(reuse.energy <= base.energy)
    # monotone in frac: more residency can only absorb more refetch
    mid = evaluate_batch(op, with_streaming_reuse(ARCH3, 0.25), mappings,
                         pairs)
    assert np.all(reuse.dram_bits <= mid.dram_bits)


def test_reuse_term_bit_identical_across_planes():
    """The four evaluator planes agree bit-for-bit with the reuse term
    enabled — same contract the equivalence suite pins for the base
    model."""
    arch = with_streaming_reuse(ARCH3, 0.5)
    fast = dataclasses.replace(FAST, max_pairs=4)
    wl = build_llm(LLMSpec("reuse-eq", 1, 128, 256, 4), seq=64,
                   act_density=0.5, w_density=0.25)

    def fingerprint(res):
        return (res.design.pattern_i, res.design.pattern_w,
                res.design.energy, res.design.cycles, res.evaluations,
                tuple((str(o.mapping), str(o.fmt_i), str(o.fmt_w))
                      for o in res.design.ops))

    with memo.disabled():
        fps = [fingerprint(cosearch(wl, arch, cfg)) for cfg in (
            dataclasses.replace(fast, use_batch=False),
            dataclasses.replace(fast, use_gather=False),
            fast,
            dataclasses.replace(fast, eval_threads=3),
        )]
    assert fps[0] == fps[1] == fps[2] == fps[3]


def test_with_streaming_reuse_roundtrip():
    arch = with_streaming_reuse(ARCH3, 0.5)
    assert arch.glb_resident_frac == 0.5
    again = arch_by_name(arch.name)
    assert again == arch
    with pytest.raises(ValueError):
        with_streaming_reuse(ARCH3, 1.5)


# ---------------------------------------------------------------------------
# per-level calibration
# ---------------------------------------------------------------------------

def test_fit_glb_scale_recovers_planted_coefficient():
    """Measured refetch = 1.7 × predicted on every row → the least-squares
    GLB fit is exactly 1.7 and the post-fit refetch residual collapses;
    rows with no refetch on either side leave the fit at identity."""
    rows = [CalibRow(role=f"r{i}", kind="bitmap",
                     measured_bits=100.0, predicted_bits=100.0,
                     measured_stream_bits=100.0 + 1.7 * p,
                     predicted_stream_bits=100.0 + p)
            for i, p in enumerate((50.0, 200.0, 800.0))]
    g = fit_glb_scale(rows)
    assert g == pytest.approx(1.7)
    assert all(abs(r.refetch_residual(g)) < 1e-12 for r in rows)
    assert fit_glb_scale([CalibRow(role="x", kind="nm",
                                   measured_bits=10.0, predicted_bits=10.0,
                                   measured_stream_bits=10.0,
                                   predicted_stream_bits=10.0)]) == 1.0


def test_calibrated_hardware_scales_glb_level():
    cal = calibrated_hardware(ARCH3, 1.25, glb_scale=2.0)
    assert cal.levels[0].pj_per_bit_read == pytest.approx(
        ARCH3.levels[0].pj_per_bit_read * 1.25)
    assert cal.levels[1].pj_per_bit_read == pytest.approx(
        ARCH3.levels[1].pj_per_bit_read * 2.0)
    assert cal.levels[1].pj_per_bit_write == pytest.approx(
        ARCH3.levels[1].pj_per_bit_write * 2.0)
    assert cal.levels[2:] == ARCH3.levels[2:]
    assert "+glb2" in cal.name
    # glb_scale=1 leaves the on-chip levels untouched (and unnamed)
    only_dram = calibrated_hardware(ARCH3, 1.25)
    assert only_dram.levels[1:] == ARCH3.levels[1:]
    assert "+glb" not in only_dram.name


def test_instrument_splits_distinct_vs_streamed(fp32_compute):
    """A 256-token forward tiles M at 128 → every kernel-backed role
    streams its payload exactly twice per call while crossing DRAM once:
    refetch_factor == 2, stream bits == 2 × distinct bits."""
    cfg = get_config("chatglm3-6b").reduced()
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    plan = rexec.build_exec_plan(cfg, BlockBernoulli(0.5, 32 * 32),
                                 tokens=256, search_cfg=FAST,
                                 value_bits=32)
    pruned = rexec.prune_params(params, plan, cfg)
    store = rexec.compress_params(pruned, plan, cfg)
    cm = rexec.CompressedModel(model, store)
    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab, (1, 256)), jnp.int32)
    with rexec.instrument() as counters:
        cm.hidden_states(pruned, toks)
    kernel_roles = [op.role for op in plan.ops
                    if op.choice.kind in ("bitmap", "nm")]
    assert kernel_roles
    for role in kernel_roles:
        c = counters[role]
        assert c.refetch_factor == pytest.approx(2.0)
        assert c.w_stream_bits == pytest.approx(2.0 * c.w_distinct_bits)
        assert c.w_stream_bits_per_call == pytest.approx(
            2.0 * c.w_distinct_bits / c.calls)


def test_predicted_stream_bits_use_mapping_refetch():
    """The plan's predicted stream traffic is distinct fetch × the
    mapping's W-irrelevant outer-loop product — spot-check the
    ``irrelevant_refetch`` helper the plan builder uses."""
    # W is (N, K): loops over M outside W's innermost relevant loop refetch
    assert irrelevant_refetch(("M", "N", "K"), "W",
                              {"M": 4, "N": 2, "K": 3}) == 4.0
    assert irrelevant_refetch(("N", "K", "M"), "W",
                              {"M": 4, "N": 2, "K": 3}) == 1.0
    cfg = get_config("chatglm3-6b").reduced()
    plan = rexec.build_exec_plan(cfg, BlockBernoulli(0.5, 32 * 32),
                                 tokens=64, search_cfg=FAST, value_bits=32)
    for op in plan.ops:
        assert op.predicted_w_stream_bits >= op.predicted_w_fetch_bits > 0


# ---------------------------------------------------------------------------
# durable memo snapshots
# ---------------------------------------------------------------------------

def _small_search():
    wl = build_llm(LLMSpec("memo-snap", 1, 128, 256, 4), seq=64,
                   act_density=0.5, w_density=0.25)
    return cosearch(wl, ARCH3, dataclasses.replace(FAST, max_pairs=4))


def test_memo_snapshot_roundtrip(tmp_path):
    """save → clear → load replays the search entirely from the snapshot
    (zero fresh evaluations), bit-identically."""
    path = str(tmp_path / "memo.pkl")
    memo.clear()
    cold = _small_search()
    n = memo.save(path)
    assert n > 0
    memo.clear()
    assert memo.load(path) is True
    warm = _small_search()
    assert warm.stats.fresh_evaluations == 0
    assert (warm.design.energy, warm.design.cycles, warm.evaluations) == \
        (cold.design.energy, cold.design.cycles, cold.evaluations)


def test_memo_snapshot_rejects_stale(tmp_path):
    path = str(tmp_path / "memo.pkl")
    memo.clear()
    _small_search()
    keys_before = memo.key_snapshot(["search_op"])["search_op"]
    memo.save(path)
    with open(path, "rb") as f:
        snap = pickle.load(f)
    # a snapshot written by different code must be ignored, not replayed
    snap["fingerprint"] = "0" * 64
    with open(path, "wb") as f:
        pickle.dump(snap, f)
    memo.clear()
    assert memo.load(path) is False
    assert memo.key_snapshot(["search_op"])["search_op"] == set()
    # wrong version and unreadable files are equally non-fatal
    snap["fingerprint"] = memo.code_fingerprint()
    snap["version"] = -1
    with open(path, "wb") as f:
        pickle.dump(snap, f)
    assert memo.load(path) is False
    with open(path, "wb") as f:
        f.write(b"not a pickle")
    assert memo.load(path) is False
    assert memo.load(str(tmp_path / "missing.pkl")) is False
    # sanity: an untampered snapshot still round-trips (re-search first —
    # the stale loads above left the cleared caches empty)
    _small_search()
    memo.save(path)
    memo.clear()
    assert memo.load(path) is True
    assert memo.key_snapshot(["search_op"])["search_op"] == keys_before


# ---------------------------------------------------------------------------
# threaded per-op search
# ---------------------------------------------------------------------------

def test_op_workers_bit_identical():
    """Serial vs threaded per-op loop: same design, same metric, same
    SearchStats, same memo counters — for several worker counts, warm and
    cold."""
    wl = build_llm(LLMSpec("op-workers", 2, 128, 256, 4), seq=64,
                   act_density=0.5, w_density=0.25)
    base = dataclasses.replace(FAST, max_pairs=4)

    def run(cfg):
        memo.clear()
        memo.reset_stats()
        res = cosearch(wl, ARCH3, cfg)
        st = memo.stats()["search_op"]
        return (res.design.energy, res.design.cycles, res.design.edp,
                res.evaluations, res.stats.evaluations,
                res.stats.fresh_evaluations, st.hits, st.misses,
                tuple((o.op.name, str(o.mapping), str(o.fmt_i),
                       str(o.fmt_w)) for o in res.design.ops))

    serial = run(base)
    for w in (2, 5):
        assert run(dataclasses.replace(base, op_workers=w)) == serial
    with memo.disabled():
        s = cosearch(wl, ARCH3, base)
        p = cosearch(wl, ARCH3, dataclasses.replace(base, op_workers=3))
    assert (s.design.edp, s.evaluations, s.stats.fresh_evaluations) == \
        (p.design.edp, p.evaluations, p.stats.fresh_evaluations)


def test_op_workers_normalized_out_of_cache_key():
    op = MatMul("m", 64, 96, 64, Bernoulli(0.5), Bernoulli(0.5))
    k1 = _search_op_key(op, ARCH3, None, None, FAST)
    k2 = _search_op_key(op, ARCH3, None, None,
                        dataclasses.replace(FAST, op_workers=8,
                                            eval_threads=2))
    assert k1 is not None and k1 == k2


# ---------------------------------------------------------------------------
# serving-plane regression: kernel cache sharing + padding extremes
# ---------------------------------------------------------------------------

def _mixed_serving(cfg, density=0.1):
    """A serving setup whose layer 0 weights are fully DENSE while the
    remaining layers realize ``density`` — the worst case for the stacked
    store's pad-to-max layout and for per-layer kernel-cache keying."""
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    plan = rexec.build_exec_plan(cfg, BlockBernoulli(density, 32 * 32),
                                 tokens=64, search_cfg=FAST, value_bits=32)
    pruned = rexec.prune_params(params, plan, cfg)
    mixed = dict(pruned)
    mixed["blocks"] = dict(pruned["blocks"])
    for op in plan.ops:
        if op.choice.kind != "bitmap":
            continue
        group, leaf = _role_path(op.role)
        mixed["blocks"][group] = dict(mixed["blocks"][group])
        w = mixed["blocks"][group][leaf]
        mixed["blocks"][group][leaf] = w.at[0].set(
            params["blocks"][group][leaf][0])
    store = rexec.compress_params(mixed, plan, cfg)
    return model, plan, mixed, store


def test_kernel_cache_shared_between_scanned_and_unrolled(fp32_compute):
    """Both serving paths dispatch every role with the per-role
    ACROSS-LAYERS max ``t_max``, so the unrolled forward reuses exactly
    the scanned forward's jitted-kernel entries — even when layer 0 is
    dense and the rest are 90% sparse (maximally different per-layer
    bounds).  A per-layer ``t_max`` would fork entries here."""
    cfg = get_config("chatglm3-6b").reduced()
    model, plan, mixed, store = _mixed_serving(cfg)
    assert any(op.choice.kind == "bitmap" for op in plan.ops)
    cm = rexec.CompressedModel(model, store)
    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab, (2, 8)), jnp.int32)

    kops.clear_kernel_cache()
    cm.hidden_states(mixed, toks)
    after_scan = kops.kernel_cache_stats()
    cm.hidden_states_unrolled(mixed, toks)
    after_both = kops.kernel_cache_stats()
    assert after_scan["entries"] > 0
    assert after_both["entries"] == after_scan["entries"], \
        "unrolled forward forked new kernel configurations"
    # the unrolled pass made only cache HITS (n_layers per role beyond
    # the scanned trace's own lookups)
    assert after_both["misses"] == after_scan["misses"]
    assert after_both["hits"] > after_scan["hits"]


def test_padding_overhead_extreme_accounted_exactly(fp32_compute):
    """One dense layer forces the stacked bitmap payloads to pad every
    sparse layer up to the full block count: the overhead is large, its
    accounting matches a by-hand recomputation from the per-layer store,
    and the padded scanned forward still decodes bit-identically to the
    per-layer dispatch."""
    # widths at which every projection spans two lane-aligned blocks: the
    # 128-wide attention weights of reduced() are one block and stay whole
    cfg = dataclasses.replace(get_config("chatglm3-6b").reduced(),
                              d_model=512, d_head=64, d_ff=1024)
    model, plan, mixed, store = _mixed_serving(cfg)
    st = stack_store(store)

    checked = 0
    for role, sr in st.roles.items():
        if sr.kind != "bitmap":
            continue
        per_layer = [store.get(layer, role)
                     for layer in range(cfg.n_layers)]
        nnzbs = [int(np.asarray(e.data.counts).sum()) for e in per_layer]
        full = (sr.n // sr.bn) * (sr.k // sr.bk)
        assert nnzbs[0] == full, "layer 0 should keep every block"
        assert max(nnzbs[1:]) < full, "sparse layers should drop blocks"
        # pad-to-max layout: every layer's payload slab is layer 0's size
        assert sr.data["blocks"].shape[:2] == (cfg.n_layers, full)
        # exact accounting: padded = stored + zero-fill payload bits
        vb = sr.data["blocks"].dtype.itemsize * 8
        pad_blocks = cfg.n_layers * full - sum(nnzbs)
        assert sr.padded_bits == pytest.approx(
            sr.stored_bits + pad_blocks * sr.bn * sr.bk * vb)
        assert sr.stored_bits == pytest.approx(
            sum(e.stored_bits for e in per_layer))
        checked += 1
    assert checked > 0
    # one dense + one ~10%-dense layer: padding inflates the store well
    # past the per-layer encoding (the reduced model's small per-tensor
    # block counts quantize the sparse layers' realized density upward,
    # which caps the contrast below the asymptotic ~2x)
    assert st.padding_overhead() > 1.25

    # padded zero blocks sit beyond every column's counts, so decoding a
    # padded layer slice is BIT-identical to the layer's own unpadded
    # encoding — kernel-level, where "identical" is well-defined
    rng = np.random.default_rng(1)
    for role, sr in st.roles.items():
        if sr.kind != "bitmap":
            continue
        x = jnp.asarray(rng.normal(size=(16, sr.n)).astype(np.float32))
        for layer in range(cfg.n_layers):
            own = store.get(layer, role).data
            padded = kops.BitmapCompressed(
                blocks=sr.data["blocks"][layer],
                counts=sr.data["counts"][layer],
                row_ids=sr.data["row_ids"][layer],
                offsets=sr.data["offsets"][layer],
                n=sr.n, k=sr.k, bn=sr.bn, bk=sr.bk, max_per_col=sr.t_max)
            y_pad = kops.bitmap_spmm(x, padded, bm=16, t_max=sr.t_max)
            y_own = kops.bitmap_spmm(x, own, bm=16, t_max=sr.t_max)
            assert np.array_equal(np.asarray(y_pad), np.asarray(y_own)), \
                (role, layer)

    # end-to-end the padded scanned forward tracks the per-layer dispatch
    # (bitwise equality is NOT guaranteed here — XLA fuses the dense glue
    # differently under scan — so pin a tight tolerance)
    cm = rexec.CompressedModel(model, store)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (2, 8)), jnp.int32)
    y_scan = cm.hidden_states(mixed, toks)
    y_unrolled = cm.hidden_states_unrolled(mixed, toks)
    np.testing.assert_allclose(np.asarray(y_scan), np.asarray(y_unrolled),
                               rtol=1e-5, atol=1e-5)
