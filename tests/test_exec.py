"""Execution-plane tests: plan round-trips, compressed-forward numerics,
measured-vs-predicted calibration, kernel jit-cache reuse, fallbacks.

The numerics contract: with fp32 compute (COMPUTE_DTYPE patched), the
compressed forward routes every planned projection through the Pallas
kernels (interpret mode on CPU) and must match the dense forward on the
SAME pruned weights within fp32 tolerance — the surrounding forward is the
dense model's own code path, so any disagreement is kernel error."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import exec as rexec
from repro.configs import get_config
from repro.core.cosearch import CoSearchConfig
from repro.core.engine import EngineConfig
from repro.core.formats import standard_formats
from repro.core.sparsity import NM, Bernoulli, BlockBernoulli
from repro.core.workload import MatMul
from repro.exec import plans
from repro.exec.calibrate import calibrated_hardware
from repro.kernels import ops as kops
from repro.models import attention as attn_mod
from repro.models import layers as L
from repro.models.transformer import Model

FAST = CoSearchConfig(objective="edp",
                      engine=EngineConfig(max_levels=2,
                                          max_allocs_per_pattern=16),
                      spatial_top=2, max_pairs=6)

BLOCK = BlockBernoulli(0.5, 32 * 32)


@pytest.fixture()
def fp32_compute(monkeypatch):
    """Run the model layers in fp32 so kernel-vs-einsum comparisons are
    accumulation-order-only (the bf16 default adds cast noise)."""
    monkeypatch.setattr(L, "COMPUTE_DTYPE", jnp.float32)
    monkeypatch.setattr(attn_mod, "COMPUTE_DTYPE", jnp.float32)


def _cfg():
    return get_config("chatglm3-6b").reduced()


def _lane_cfg():
    """The smallest chatglm3-6b cut in which every projection spans two
    lane-aligned (128-multiple) bitmap blocks, so block pruning can realize
    the plan's 0.5 density: in ``_cfg()`` the 128-wide attention weights are
    one Mosaic block each and stay whole."""
    return dataclasses.replace(_cfg(), d_model=512, d_head=64, d_ff=1024)


def _plan(cfg, sp):
    return rexec.build_exec_plan(cfg, sp, tokens=64, search_cfg=FAST,
                                 value_bits=32)


def _serving(cfg, sp, seed=0):
    model = Model(cfg)
    params = model.init(jax.random.key(seed))
    plan = _plan(cfg, sp)
    pruned = rexec.prune_params(params, plan, cfg)
    store = rexec.compress_params(pruned, plan, cfg)
    return model, plan, pruned, store


def _tokens(cfg, b=2, s=16, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, cfg.vocab, (b, s)), jnp.int32)


# ---------------------------------------------------------------------------
# plans
# ---------------------------------------------------------------------------

def test_exec_plan_covers_all_model_roles():
    cfg = _cfg()
    plan = _plan(cfg, BLOCK)
    assert {op.role for op in plan.ops} == \
        {r.role for r in cfg.matmul_roles()}
    for op in plan.ops:
        assert op.choice.kind in ("bitmap", "nm", "dense")
        if op.choice.kind == "bitmap":
            assert op.n % op.choice.block_n == 0
            assert op.k % op.choice.block_k == 0


def test_exec_plan_moe_fanout():
    cfg = get_config("qwen3-moe-30b-a3b").reduced()
    wl = plans.model_workload(cfg, tokens=64, w_sparsity=BLOCK)
    by_name = {op.name: op for op in wl.ops}
    assert "moe.w_gate" in by_name and "ffn.w_gate" not in by_name
    moe_op = by_name["moe.w_gate"]
    assert moe_op.count == cfg.n_layers * cfg.moe.n_experts
    # per-expert routed tokens, not the full batch
    assert moe_op.M == max(1, int(64 * cfg.moe.top_k / cfg.moe.n_experts))


def test_exec_plan_json_roundtrip_bit_identical(tmp_path):
    cfg = _cfg()
    plan = _plan(cfg, BLOCK)
    path = tmp_path / "plan.json"
    path.write_text(plan.to_json())
    loaded = rexec.ExecPlan.from_json(path.read_text())
    # dataclass equality covers every field bit-exactly (floats round-trip
    # through repr); `search` is excluded from equality by design
    assert loaded == plan
    assert [op.choice for op in loaded.ops] == [op.choice for op in plan.ops]
    assert loaded.to_json() == plan.to_json()


def test_translate_blocks_lane_aligned_and_vmem_capped_at_published_widths():
    """At chatglm3-6b's widths the searched leaves (1024x4096, 856x4096,
    ...) are refused by Mosaic; translate serves every role with
    128-multiple blocks that divide the extents and fit the VMEM budget,
    and keeps the searched format string."""
    from repro.kernels import tiling
    from repro.launch.serve import _fast_plan
    cfg = dataclasses.replace(get_config("chatglm3-6b"), n_layers=4)
    plan = _fast_plan(cfg, tokens=64)
    assert {op.role for op in plan.ops} == \
        {r.role for r in cfg.matmul_roles()}
    for op in plan.ops:
        ch = op.choice
        assert ch.kind == "bitmap" and ch.format_str.count("B(") == 1
        for block, extent in ((ch.block_n, op.n), (ch.block_k, op.k)):
            assert block % tiling.LANE == 0 and extent % block == 0, op
        assert tiling.bitmap_tile_bytes(
            ch.block_n, ch.block_k, plan.value_bits // 8) \
            <= tiling.VMEM_BUDGET_BYTES, op


def test_fallback_reason_recorded_for_unservable_formats():
    op = MatMul("w", 64, 128, 128, Bernoulli(0.5), Bernoulli(0.3))
    rle = standard_formats({"N": 128, "K": 128})["RLE"]
    ch = plans.translate(op, rle, Bernoulli(0.3))
    assert ch.kind == "dense"
    assert ch.fallback is not None and ch.fallback.code == "no_tpu_kernel"
    assert "RLE" in ch.fallback.detail
    # the search itself choosing dense is NOT a fallback
    assert plans.translate(op, None, Bernoulli(0.3)).fallback is None
    # fallbacks surface on the plan
    plan = dataclasses.replace(
        _plan(_cfg(), BLOCK),
        ops=(plans.OpPlan(role="x", m=1, n=128, k=128, count=1.0, choice=ch,
                          tile={}, predicted_w_fetch_bits=0.0,
                          predicted_i_fetch_bits=0.0, predicted_dram_bits=0.0,
                          predicted_energy=0.0),))
    assert plan.fallbacks() == {"x": ch.fallback}


# ---------------------------------------------------------------------------
# compress
# ---------------------------------------------------------------------------

def test_compress_store_exact_ratio_accounting():
    cfg = _lane_cfg()
    model, plan, pruned, store = _serving(cfg, BLOCK)
    assert len(store) == cfg.n_layers * len(plan.ops)
    for e in store:
        if e.kind != "bitmap":
            continue
        d = e.data
        nnzb = int(np.asarray(d.counts).sum())
        gn, gk = d.n // d.bn, d.k // d.bk
        # exact: realized payload bits + one bitmap bit per grid block
        assert e.stored_bits == nnzb * d.bn * d.bk * 32 + gn * gk
        assert e.dense_bits == d.n * d.k * 32
    # block pruning at density 0.5 halves the payload (+ metadata epsilon)
    total = store.achieved_ratio()
    assert 0.45 < total < 0.55


# ---------------------------------------------------------------------------
# dispatch numerics (the acceptance contract)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sp,kinds", [(BLOCK, {"bitmap"}),
                                      (NM(2, 4), {"nm"})])
def test_compressed_forward_matches_dense(fp32_compute, sp, kinds):
    cfg = _cfg()
    model, plan, pruned, store = _serving(cfg, sp)
    assert {op.choice.kind for op in plan.ops} == kinds
    tokens = _tokens(cfg)
    dense_out = model.hidden_states(pruned, tokens, remat=False)
    comp_out = rexec.CompressedModel(model, store).hidden_states(pruned,
                                                                 tokens)
    np.testing.assert_allclose(np.asarray(comp_out), np.asarray(dense_out),
                               rtol=1e-4, atol=1e-4)


def test_compressed_forward_bf16_default_close():
    """Without the fp32 patch the only divergence is kernel fp32
    accumulation vs bf16 einsum — bounded by bf16 resolution."""
    cfg = _cfg()
    model, plan, pruned, store = _serving(cfg, BLOCK)
    tokens = _tokens(cfg)
    dense_out = model.hidden_states(pruned, tokens, remat=False)
    comp_out = rexec.CompressedModel(model, store).hidden_states(pruned,
                                                                 tokens)
    np.testing.assert_allclose(np.asarray(comp_out, np.float32),
                               np.asarray(dense_out, np.float32),
                               rtol=5e-2, atol=1e-1)


def test_dispatch_jit_cache_shared_across_layers(fp32_compute):
    cfg = _cfg()
    model, plan, pruned, store = _serving(cfg, BLOCK)
    cm = rexec.CompressedModel(model, store)
    # unrolled reference path: every (layer, role) projection dispatched,
    # but only the distinct static configurations built a wrapper —
    # repeated layers are hits (one per-role t_max shared across layers)
    kops.clear_kernel_cache()
    cm.hidden_states_unrolled(pruned, _tokens(cfg))
    stats = kops.kernel_cache_stats()
    assert stats["hits"] > 0
    assert stats["entries"] <= len(plan.ops)
    assert stats["hits"] + stats["misses"] == cfg.n_layers * len(plan.ops)
    # scanned path: the hook runs ONCE per role per trace (the compiled
    # scan replays it per layer), so a whole forward costs len(plan.ops)
    # cache lookups — not n_layers × that
    kops.clear_kernel_cache()
    cm.hidden_states(pruned, _tokens(cfg))
    stats = kops.kernel_cache_stats()
    assert stats["hits"] + stats["misses"] == len(plan.ops)
    assert stats["entries"] <= len(plan.ops)


def test_kernel_wrapper_cache_reuses_jit():
    kops.clear_kernel_cache()
    rng = np.random.default_rng(0)
    w = rng.normal(size=(64, 64)).astype(np.float32)
    comp = kops.compress_bitmap(w, 16, 16)
    x = jnp.asarray(rng.normal(size=(16, 64)).astype(np.float32))
    y1 = kops.bitmap_spmm(x, comp, bm=16)
    st1 = kops.kernel_cache_stats()
    y2 = kops.bitmap_spmm(x, comp, bm=16)
    st2 = kops.kernel_cache_stats()
    assert st1 == {"hits": 0, "misses": 1, "entries": 1}
    assert st2 == {"hits": 1, "misses": 1, "entries": 1}
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2))


# ---------------------------------------------------------------------------
# calibration (end-to-end acceptance)
# ---------------------------------------------------------------------------

def test_end_to_end_plan_dispatch_calibration(fp32_compute):
    """Searched plan → compressed forward → measured counters vs the cost
    model's predicted fetch terms, within the calibrated bound."""
    cfg = _lane_cfg()
    model, plan, pruned, store = _serving(cfg, BLOCK)
    tokens = _tokens(cfg)
    dense_out = model.hidden_states(pruned, tokens, remat=False)
    with rexec.instrument() as counters:
        comp_out = rexec.CompressedModel(model, store).hidden_states(
            pruned, tokens)
    # (a) outputs match the dense forward
    np.testing.assert_allclose(np.asarray(comp_out), np.asarray(dense_out),
                               rtol=1e-4, atol=1e-4)
    # every planned role was dispatched once per layer
    assert {r for r in counters} == {op.role for op in plan.ops}
    assert all(c.calls == cfg.n_layers for c in counters.values())

    # (b) measured fetched bits vs predicted fetch terms
    report = rexec.calibrate(cfg, plan, counters, search_cfg=FAST)
    rows = report.rows
    assert {r.role for r in rows} == {op.role for op in plan.ops}
    for r in rows:
        assert r.measured_bits > 0 and r.predicted_bits > 0
    # the BlockBernoulli spec models block pruning faithfully: the fitted
    # energy coefficient is ~1 and post-fit residuals are tight
    assert 0.9 < report.scale < 1.1
    assert report.max_residual < 0.05
    assert abs(report.energy_drift) < 0.1
    assert report.calibrated_plan.ops


def test_calibration_catches_iid_model_drift(fp32_compute):
    """A plan searched under i.i.d. Bernoulli expects fine-grained
    compression wins the MXU-aligned executable blocks cannot realize
    (whole 128-wide blocks are kept once any element survives), so
    measured traffic comes in well ABOVE prediction; the fitted scale
    raises the DRAM coefficient and the re-searched predicted energy
    drifts up accordingly."""
    cfg = _cfg()
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    plan = _plan(cfg, Bernoulli(0.5))
    assert any(op.choice.kind == "bitmap" for op in plan.ops)
    pruned = rexec.prune_params(params, plan, cfg)
    store = rexec.compress_params(pruned, plan, cfg)
    with rexec.instrument() as counters:
        rexec.CompressedModel(model, store).hidden_states(pruned,
                                                          _tokens(cfg))
    report = rexec.calibrate(cfg, plan, counters, search_cfg=FAST)
    assert report.scale > 1.3                  # measured ≫ predicted
    assert report.max_rel_err > 0.5            # the drift was real…
    assert report.max_residual < report.max_rel_err   # …the fit shrinks it
    assert report.energy_drift > 0.2           # calibrated search sees it


def test_calibrated_hardware_scales_dram_only():
    arch = plans.TPUV5E
    cal2 = calibrated_hardware(arch, 0.5)
    assert cal2.dram.pj_per_bit == pytest.approx(arch.dram.pj_per_bit * 0.5)
    assert cal2.glb.pj_per_bit == arch.glb.pj_per_bit
    assert cal2.name.startswith(arch.name)


def test_calibrated_plan_resolves_hardware_after_roundtrip(fp32_compute):
    """A calibrated plan keeps the BASE arch name + the fit as
    ``energy_scale``, so hardware() resolves (with the scale re-applied)
    even after a JSON round trip — and a second calibration composes."""
    cfg = _cfg()
    model, plan, pruned, store = _serving(cfg, BLOCK)
    with rexec.instrument() as counters:
        rexec.CompressedModel(model, store).hidden_states(pruned,
                                                          _tokens(cfg))
    report = rexec.calibrate(cfg, plan, counters, search_cfg=FAST)
    cal_plan = report.calibrated_plan
    assert cal_plan.arch == plan.arch                  # base name kept
    assert cal_plan.energy_scale == pytest.approx(report.scale)
    hw = cal_plan.hardware()
    assert hw.dram.pj_per_bit == pytest.approx(
        plan.hardware().dram.pj_per_bit * report.scale)
    loaded = rexec.ExecPlan.from_json(cal_plan.to_json())
    assert loaded == cal_plan
    assert loaded.hardware().dram.pj_per_bit == hw.dram.pj_per_bit
    # round 2 uses the same counters: composes on top of round 1's scale
    report2 = rexec.calibrate(cfg, cal_plan, counters, search_cfg=FAST)
    assert report2.calibrated_plan.energy_scale == pytest.approx(
        report.scale * report2.scale)


def test_nm_plan_parameters_thread_through_prune_and_compress(fp32_compute):
    """An NM(1, 4) plan must serve 1:4 weights, not the 2:4 defaults."""
    from repro.sparse import masks

    cfg = _cfg()
    model, plan, pruned, store = _serving(cfg, NM(1, 4))
    assert all(op.choice.kind == "nm" for op in plan.ops)
    assert plan.ops[0].choice.format_str == "CP(1:4)"
    w = pruned["blocks"]["attn"]["wq"][0]
    assert masks.density(w) == pytest.approx(0.25, abs=0.01)
    e = store.get(0, "attn.wq")
    assert e.data.n_sel == 1 and e.data.m_group == 4
    # 1/4 of values at fp32 + 2-bit indices ≈ 0.266 — and the plan's
    # predicted ratio (value_bits=32) says the same
    assert e.achieved_ratio == pytest.approx(0.25 * (1 + 2 / 32), rel=1e-3)
    assert plan.ops[0].choice.predicted_ratio == pytest.approx(
        e.achieved_ratio, rel=1e-3)
    # and the 1:4 forward still matches dense
    tokens = _tokens(cfg)
    dense_out = model.hidden_states(pruned, tokens, remat=False)
    comp_out = rexec.CompressedModel(model, store).hidden_states(pruned,
                                                                 tokens)
    np.testing.assert_allclose(np.asarray(comp_out), np.asarray(dense_out),
                               rtol=1e-4, atol=1e-4)
