"""Serving-plane tests: scan-compiled compressed prefill / KV-cache decode.

Contracts pinned here:

  * dense ``Model.prefill`` fills the SAME cache the token-by-token decode
    ingest builds, and its logits match the full forward;
  * compressed ``decode_step`` logits match compressed ``prefill`` logits
    position by position (bitmap and N:M plans);
  * batch-of-N serving equals N stacked batch-of-1 runs;
  * ``CompressedModel.generate`` emits BIT-IDENTICAL tokens to the dense
    model's greedy decode at fp32 on an all-bitmap plan (the acceptance
    gate: compressed serving changes the numerics only by kernel
    accumulation order, which greedy argmax absorbs);
  * the scanned forward's instrument() counters equal the unrolled
    per-layer loop's (per-trace recording semantics);
  * the layer-stacked store pads bitmap payloads without changing exact
    accounting, and ``t_max`` keys the kernel jit cache.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import exec as rexec
from repro.configs import get_config
from repro.core.cosearch import CoSearchConfig
from repro.core.engine import EngineConfig
from repro.core.sparsity import NM, BlockBernoulli
from repro.exec.compress import stack_store
from repro.kernels import ops as kops
from repro.launch import serve
from repro.launch.mesh import make_serve_mesh
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
    monkeypatch.setattr(L, "COMPUTE_DTYPE", jnp.float32)
    monkeypatch.setattr(attn_mod, "COMPUTE_DTYPE", jnp.float32)


def _cfg():
    return get_config("chatglm3-6b").reduced()


def _serving(cfg, sp, seed=0):
    model = Model(cfg)
    params = model.init(jax.random.key(seed))
    plan = rexec.build_exec_plan(cfg, sp, tokens=64, search_cfg=FAST,
                                 value_bits=32)
    pruned = rexec.prune_params(params, plan, cfg)
    store = rexec.compress_params(pruned, plan, cfg)
    return model, plan, pruned, store


def _tokens(cfg, b=2, s=8, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, cfg.vocab, (b, s)), jnp.int32)


# ---------------------------------------------------------------------------
# dense prefill
# ---------------------------------------------------------------------------

def test_prefill_matches_hidden_states_and_decode_ingest(fp32_compute):
    cfg = _cfg()
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    toks = _tokens(cfg, b=2, s=8)
    max_len = 12

    logits, cache = model.prefill(params, toks, max_len)
    assert logits.shape == (2, 8, cfg.vocab)
    assert cache["self"]["k"].shape[2] == max_len

    # last-position logits == the full forward's logits head
    x = model.hidden_states(params, toks, remat=False)
    ref = jnp.einsum("btd,vd->btv", x,
                     params["embed"].astype(L.COMPUTE_DTYPE))
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)

    # the cache equals the token-by-token decode ingest's
    cache2 = model.init_cache(2, max_len)
    lg = None
    for t in range(8):
        lg, cache2 = model.decode_step(params, cache2, toks[:, t],
                                       jnp.asarray(t, jnp.int32))
    np.testing.assert_allclose(np.asarray(cache["self"]["k"]),
                               np.asarray(cache2["self"]["k"]),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(cache["self"]["v"]),
                               np.asarray(cache2["self"]["v"]),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(logits[:, -1]), np.asarray(lg),
                               rtol=1e-4, atol=1e-4)


def test_prefill_unsupported_families_fall_back():
    cfg = dataclasses.replace(_cfg(), window=16)   # ring cache → no prefill
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    toks = _tokens(cfg, b=1, s=4)
    with pytest.raises(NotImplementedError):
        model.prefill(params, toks, 8)
    # generate still serves via the exact token-by-token ingest
    out, t_pref, t_gen = serve.generate(model, params, toks, 3, 8)
    assert out.shape == (1, 3)


# ---------------------------------------------------------------------------
# compressed prefill / decode parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sp", [BLOCK, NM(2, 4)],
                         ids=["bitmap", "nm"])
def test_compressed_decode_matches_compressed_prefill(fp32_compute, sp):
    cfg = _cfg()
    model, plan, pruned, store = _serving(cfg, sp)
    cm = rexec.CompressedModel(model, store)
    toks = _tokens(cfg, b=2, s=8)
    max_len = 10

    logits, _ = cm.prefill(pruned, toks, max_len)
    cache = cm.init_cache(2, max_len)
    for t in range(8):
        lg, cache = cm.decode_step(pruned, cache, toks[:, t],
                                   jnp.asarray(t, jnp.int32))
        # decode_step at position t sees exactly prefill's prefix ≤ t
        np.testing.assert_allclose(np.asarray(lg),
                                   np.asarray(logits[:, t]),
                                   rtol=1e-4, atol=1e-4,
                                   err_msg=f"position {t}")


@pytest.mark.parametrize("sp", [BLOCK, NM(2, 4)],
                         ids=["bitmap", "nm"])
def test_batch_of_n_equals_stacked_batch_of_1(fp32_compute, sp):
    cfg = _cfg()
    model, plan, pruned, store = _serving(cfg, sp)
    cm = rexec.CompressedModel(model, store)
    prompts = _tokens(cfg, b=3, s=8)
    gen = 4

    batched, _, _ = cm.generate(pruned, prompts, gen)
    singles = [cm.generate(pruned, prompts[i:i + 1], gen)[0]
               for i in range(3)]
    stacked = jnp.concatenate(singles, axis=0)
    assert bool(jnp.all(batched == stacked)), (
        f"batched={np.asarray(batched)} singles={np.asarray(stacked)}")


def test_generate_bit_identical_dense_vs_compressed(fp32_compute):
    """Acceptance: greedy tokens from the compressed scan equal the dense
    model's, bit for bit, on an all-bitmap plan at fp32."""
    cfg = _cfg()
    model, plan, pruned, store = _serving(cfg, BLOCK)
    assert all(op.choice.kind == "bitmap" for op in plan.ops)
    cm = rexec.CompressedModel(model, store)
    prompts = _tokens(cfg, b=2, s=8)
    gen = 6

    toks_d, _, _ = serve.generate(model, pruned, prompts, gen, 8 + gen)
    toks_c, _, _ = cm.generate(pruned, prompts, gen)
    assert toks_c.shape == (2, gen)
    assert bool(jnp.all(toks_d == toks_c)), (
        f"dense={np.asarray(toks_d)} compressed={np.asarray(toks_c)}")


def test_serve_smoke_batched_decode():
    """Tiny end-to-end serve: batch 2, 4 decode steps, default dtypes,
    through the shared generate driver (mesh helper engaged when devices
    allow)."""
    cfg = _cfg()
    model = Model(cfg)
    params = model.init(jax.random.key(1))
    prompts = _tokens(cfg, b=2, s=4, seed=3)
    mesh = make_serve_mesh(2)
    out, t_pref, t_gen = serve.generate(model, params, prompts, 4, 8,
                                        mesh=mesh)
    assert out.shape == (2, 4)
    assert out.dtype == jnp.int32
    assert bool(jnp.all((out >= 0) & (out < cfg.vocab)))
    assert t_pref > 0 and t_gen > 0


# ---------------------------------------------------------------------------
# counters under scan
# ---------------------------------------------------------------------------

def test_instrument_scanned_matches_unrolled(fp32_compute):
    """Per-trace recording: ONE scanned forward records the same per-role
    totals as the unrolled per-layer loop — calibrate fits the same
    coefficients on either path."""
    cfg = _cfg()
    model, plan, pruned, store = _serving(cfg, BLOCK)
    cm = rexec.CompressedModel(model, store)
    toks = _tokens(cfg)

    with rexec.instrument() as scanned:
        cm.hidden_states(pruned, toks)
    with rexec.instrument() as unrolled:
        cm.hidden_states_unrolled(pruned, toks)

    assert set(scanned) == set(unrolled) == {op.role for op in plan.ops}
    for role in scanned:
        s, u = scanned[role], unrolled[role]
        assert s.calls == u.calls == cfg.n_layers
        assert s.w_fetch_bits == pytest.approx(u.w_fetch_bits)
        assert s.x_bits == pytest.approx(u.x_bits)
        assert s.y_bits == pytest.approx(u.y_bits)
        assert s.macs == pytest.approx(u.macs)
        assert s.decode_ops == pytest.approx(u.decode_ops)
        assert s.w_fetch_bits_per_call == pytest.approx(
            u.w_fetch_bits_per_call)


# ---------------------------------------------------------------------------
# stacked store
# ---------------------------------------------------------------------------

def test_stacked_store_padding_and_accounting():
    cfg = _cfg()
    model, plan, pruned, store = _serving(cfg, BLOCK)
    st = stack_store(store)
    assert st.n_layers == cfg.n_layers
    assert set(st.roles) == {op.role for op in plan.ops}
    extras = st.extras()
    for role, sr in st.roles.items():
        per_layer = [store.get(layer, role) for layer in range(cfg.n_layers)]
        assert sr.stored_bits == pytest.approx(
            sum(e.stored_bits for e in per_layer))
        assert sr.dense_bits == pytest.approx(
            sum(e.dense_bits for e in per_layer))
        if sr.kind == "bitmap":
            d = extras[role]
            # every stacked array leads with the layer axis
            assert all(a.shape[0] == cfg.n_layers for a in d.values())
            # padding never loses payload: the max layer fits exactly
            assert d["blocks"].shape[1] == max(
                max(int(e.data.blocks.shape[0]) for e in per_layer), 1)
            assert sr.padded_bits >= sr.stored_bits
            assert sr.t_max == max(e.data.max_per_col for e in per_layer)
    assert st.padding_overhead() >= 1.0


def test_tmax_keys_kernel_cache():
    """The stacked grid bound is part of the jitted-wrapper key: two
    dispatches differing only in t_max must not share a compiled kernel
    (one would run the wrong grid)."""
    kops.clear_kernel_cache()
    rng = np.random.default_rng(0)
    w = rng.normal(size=(64, 64)).astype(np.float32)
    comp = kops.compress_bitmap(w, 16, 16)
    x = jnp.asarray(rng.normal(size=(16, 64)).astype(np.float32))
    y1 = kops.bitmap_spmm(x, comp, bm=16, t_max=comp.max_per_col)
    y2 = kops.bitmap_spmm(x, comp, bm=16, t_max=comp.max_per_col + 1)
    stats = kops.kernel_cache_stats()
    assert stats["misses"] == 2 and stats["entries"] == 2
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# mesh helper
# ---------------------------------------------------------------------------

def test_make_serve_mesh_degenerate_cases():
    ndev = len(jax.devices())
    if ndev == 1:
        assert make_serve_mesh(8) is None          # nothing to shard over
    assert make_serve_mesh(8, model=ndev + 1) is None
    mesh = make_serve_mesh(ndev)
    if ndev > 1:
        assert mesh is not None
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        assert sizes["data"] > 1 and ndev % sizes["data"] == 0


def test_compile_cache_helper(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins untouched; otherwise the cache sits
    at the fixed in-checkout path."""
    from repro.launch import compile_cache as cc
    prev = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv(cc.ENV_VAR, "/elsewhere/jax-cache")
        assert cc.configure_compile_cache() == "/elsewhere/jax-cache"
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv(cc.ENV_VAR)
        path = cc.configure_compile_cache()
        assert path == str(cc.CHECKOUT / ".jax_cache")
        assert (cc.CHECKOUT / "src" / "repro" / "launch").is_dir()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_compressed_generate_under_mesh_runs_kernels_per_shard():
    """Under a bound serving mesh the Pallas kernels sit inside shard_map
    (XLA cannot partition a Mosaic call): tokens equal the unmeshed run."""
    import functools

    from repro.launch.mesh import _make_mesh, axis_map_for
    from repro.models.sharding import logical_axis_rules

    cfg = _cfg()
    params = Model(cfg).init(jax.random.key(0))
    cm, pruned = serve.compressed_model(cfg, params)
    prompts = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab, (2, 8)), jnp.int32)
    mesh = _make_mesh((1, 1), ("data", "model"))
    with mesh, logical_axis_rules(axis_map_for(mesh), mesh=mesh):
        jaxpr = jax.make_jaxpr(functools.partial(cm.prefill, max_len=12))(
            pruned, prompts)
    assert "shard_map" in str(jaxpr)
    meshed = serve.generate(cm, pruned, prompts, 4, 12, mesh=mesh)[0]
    plain = serve.generate(cm, pruned, prompts, 4, 12)[0]
    np.testing.assert_array_equal(np.asarray(meshed), np.asarray(plain))
