"""Routed experts at one chip's share and windowed / full layers in one
scanned stack (the ``mellum2-12b-a2.5b`` shape, at a tiny preset), served
through plan → prune → compress → stack → scanned dispatch → mixer and
compared with the benchmark's float32 reference
(``benchmarks/chip/reference/moe.py``), which shares no code with the
program."""

import dataclasses
import importlib.util
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import RopeCfg
from repro.core.sparsity import BlockBernoulli
from repro.exec import (CompressedModel, build_exec_plan, compress_params,
                        prune_params)
from repro.exec.dispatch import active_stacked, instrument
from repro.launch.mixer import Mixer, Request
from repro.models import attention as attn
from repro.models import layers as L
from repro.models import moe as moe_mod
from repro.models.transformer import Model
from repro.obs import metrics as omet

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference():
    path = os.path.join(ROOT, "benchmarks", "chip", "reference", "moe.py")
    spec = importlib.util.spec_from_file_location("reference_moe", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()
WINDOW = 8


def tiny(n_experts=16, top_k=4, held=4, first=0, window=WINDOW, **kw):
    """The reduced preset, with a window short enough for tiny prompts to
    cross it and 16 experts of which ``held`` are held here."""
    cfg = get_config("mellum2-12b-a2.5b").reduced()
    pat = tuple(dataclasses.replace(k, window=window if k.window else 0)
                for k in cfg.attn_pattern)
    moe = dataclasses.replace(cfg.moe, n_experts=n_experts, top_k=top_k,
                              n_held=held, first_expert=first)
    return dataclasses.replace(cfg, attn_pattern=pat, moe=moe, **kw)


def ref_dims(cfg) -> dict:
    m = cfg.moe
    return {"n_layers": cfg.n_layers, "head_dim": cfg.head_dim,
            "n_heads": cfg.n_heads, "n_kv_heads": cfg.n_kv_heads,
            "norm_eps": cfg.norm_eps, "top_k": m.top_k,
            "norm_topk_prob": m.norm_topk_prob,
            "first_expert": m.first_expert, "n_held": m.held,
            "windows": tuple(k.window for k in cfg.layer_attn),
            "rope": tuple((k.rope.theta, k.rope.factor, k.rope.original_max,
                           k.rope.beta_fast, k.rope.beta_slow,
                           k.rope.attention_factor) for k in cfg.layer_attn)}


def ref_weights(p) -> dict:
    """The program's tree in the reference's layout (float32)."""
    b = p["blocks"]
    w = {"embed": p["embed"], "head": p["head"],
         "final_norm": p["final_norm"], "ln1": b["ln1"], "ln2": b["ln2"]}
    w.update(b["attn"])
    w.update(b["ffn"])
    return jax.tree.map(lambda a: a.astype(jnp.float32), w)


def _params(cfg, seed=0):
    """Seeded weights with norms that are not all zero."""
    p = Model(cfg).init(jax.random.key(seed))
    keys = iter(jax.random.split(jax.random.key(seed + 100), 3))
    b = dict(p["blocks"])
    for name in ("ln1", "ln2"):
        b[name] = 0.1 * jax.random.normal(next(keys), b[name].shape)
    return dict(p, blocks=b,
                final_norm=0.1 * jax.random.normal(next(keys),
                                                   p["final_norm"].shape))


def _served(cfg, seed=0):
    plan = build_exec_plan(cfg, BlockBernoulli(0.5, 32 * 32), tokens=64,
                           value_bits=32)
    pruned = prune_params(_params(cfg, seed), plan, cfg)
    return CompressedModel(Model(cfg), compress_params(pruned, plan, cfg)), \
        pruned


# -- through the mixer, against the reference -------------------------------

def test_mixer_serves_compressed_experts_and_windows_like_the_reference():
    """Compressed prefill, then decode through the mixer with per-slot
    positions that cross the window (prompts shorter and longer than it,
    slots at different positions), against the reference's full forward
    over each prompt and its served tokens, on logits.

    Tolerances: the program's activations and KV cache are bfloat16 (the
    reference is float32 throughout), so a logit moves by the rounding of
    ~10 bf16 products per layer: 0.06 on logits that spread over ±4, for
    19 rows in 20.  A row whose router scores tie within that rounding at
    some layer may reach another expert there, which moves its logits by
    up to one expert's gated part: 0.5 for every row (this seed has one
    such row, at 0.30).  A wrong window edge, RoPE, gate, cache row or a
    dropped expert moves every row by the spread itself.  The served
    payload's dtype is checked exactly: served in bfloat16 (below the
    float32 the configuration states) this test fails there."""
    cfg = tiny()
    cm, pruned = _served(cfg)
    for sr in cm.stacked.roles.values():
        assert sr.kind == "bitmap"
        assert sr.data["blocks"].dtype == jnp.float32
    mx = Mixer(cm, pruned, slots=3, max_len=48)
    seen = []
    step, prefill = mx._step_fn, mx._prefill_fn

    def rec_step(params, cache, toks, pos):
        logits, cache = step(params, cache, toks, pos)
        uids = [r.uid if r is not None else None for r in mx._req]
        seen.append((uids, np.asarray(pos), np.asarray(logits)))
        return logits, cache

    first = {}

    def rec_prefill(params, prompt):
        logits, cache = prefill(params, prompt)
        first[tuple(np.asarray(prompt[0]))] = np.asarray(logits[0, -1])
        return logits, cache
    mx._step_fn, mx._prefill_fn = rec_step, rec_prefill

    rng = np.random.default_rng(0)
    reqs = [Request(uid=f"r{i}", max_new=n,
                    prompt=rng.integers(0, cfg.vocab, plen).astype(np.int32))
            for i, (plen, n) in enumerate([(5, 14), (13, 9), (3, 20),
                                           (21, 6)])]
    results = {r.uid: r for r in mx.run(reqs)}
    w, dims = ref_weights(pruned), ref_dims(cfg)
    first_gaps, gaps = [], []
    for req in reqs:
        toks = results[req.uid].tokens
        seq = np.concatenate([req.prompt, toks[:-1]])
        ref = np.asarray(REF.logits(w, seq, np.arange(len(seq)), dims,
                                    bucket=16))
        plen = len(req.prompt)
        first_gaps.append(np.abs(first[tuple(req.prompt)]
                                 - ref[plen - 1]).max())
        for uids, pos, logits in seen:
            for slot, uid in enumerate(uids):
                if uid == req.uid:
                    gaps.append(np.abs(logits[slot] - ref[pos[slot]]).max())
        assert np.abs(ref).max() > 1.0
    assert len(gaps) == sum(r.max_new - 1 for r in reqs)
    assert max(p + n for p, n in [(5, 14), (13, 9), (3, 20), (21, 6)]) \
        > 2 * WINDOW
    gaps = np.array(first_gaps + gaps)
    assert gaps.max() < 0.5, gaps.max()
    assert np.quantile(gaps, 0.95) < 0.06, np.quantile(gaps, 0.95)


def test_every_held_expert_is_its_own_kernel_call():
    """One bitmap kernel per layer, role and held expert; the stacked
    (layer, expert) payloads verify against the digests recorded at
    compression; the routed-row counter rides the cache and is exported."""
    cfg = tiny()
    cm, pruned = _served(cfg)
    assert cm.stacked.roles["moe.w_up"].data["blocks"].shape[:2] == (
        cfg.n_layers, cfg.moe.held)
    assert set(cm.verify().values()) == {"ok"}
    cache = cm.init_cache(2, 32)
    with instrument() as counters:
        jax.jit(cm.decode_step).lower(pruned, cache,
                                      jnp.zeros(2, jnp.int32),
                                      jnp.zeros(2, jnp.int32))
    held = cfg.moe.held
    for role in ("moe.w_gate", "moe.w_up", "moe.w_down"):
        assert counters[role].calls == cfg.n_layers * held
    for role in ("attn.wq", "attn.wk", "attn.wv", "attn.wo"):
        assert counters[role].calls == cfg.n_layers
    logits, cache = cm.decode_step(pruned, cache, jnp.array([3, 4]),
                                   jnp.array([0, 0]))
    rows = np.asarray(cache["moe_rows"]).reshape(cfg.n_layers, held)
    # two rows a step; each row reaches at most top_k of the held experts
    assert rows.sum() <= 2 * cfg.n_layers * min(cfg.moe.top_k, held)
    mx = Mixer(cm, pruned, slots=2, max_len=32)
    mx.cache = cache
    np.testing.assert_array_equal(mx.expert_rows(), rows)
    reg = omet.MetricsRegistry()
    omet.ingest_expert_rows(reg, mx.expert_rows(), cfg.moe.first_expert)
    assert reg.value("moe_routed_rows", layer=1, expert=2) == rows[1, 2]


# -- the share: expert parallelism adds up to the uncut layer ----------------

def test_four_shares_add_up_to_the_uncut_reference_layer():
    """Four chips' shares (experts 0-3, 4-7, 8-11, 12-15), attention
    counted once, give the uncut reference's layer output.  Tolerance:
    the program's activations are bfloat16 (relative rounding 2^-8 on
    values of order 1), the reference float32."""
    full = tiny(held=16)
    p = _params(full, seed=3)
    lw = {k: v[0] for k, v in ref_weights(p).items()
          if k not in ("embed", "head", "final_norm")}
    x = jax.random.normal(jax.random.key(4), (24, full.d_model))
    want = REF._layer(x, lw, tuple(sorted(ref_dims(full).items())), 0,
                      "fp32")

    blk = jax.tree.map(lambda a: a[0], p["blocks"])
    freqs = jnp.asarray(L.rope_inv_freq(full.layer_attn[0].rope,
                                        full.head_dim), jnp.float32)
    h = L.rms_norm(x.astype(L.COMPUTE_DTYPE), blk["ln1"], full.norm_eps)
    xa = x + attn.attention_block(h[None], blk["attn"], full, freqs,
                                  jnp.arange(24), window=WINDOW)[0]
    h2 = L.rms_norm(xa, blk["ln2"], full.norm_eps)
    parts = []
    for first in (0, 4, 8, 12):
        share = tiny(held=4, first=first)
        ffn = {"router": blk["ffn"]["router"],
               **{k: blk["ffn"][k][first:first + 4]
                  for k in ("w_gate", "w_up", "w_down")}}
        parts.append(moe_mod.moe_block(h2, ffn, share)[0])
    got = xa + sum(parts)
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=0.03 * scale)
    # and each share is the uncut layer's experts restricted to it
    uncut = moe_mod.moe_block(h2, blk["ffn"], full)[0]
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(uncut),
                               atol=1e-5 * scale)


# -- no row dropped ---------------------------------------------------------

@pytest.mark.parametrize("rows", [8, 64])
def test_router_sending_every_row_to_one_eight_drops_none(rows):
    """A router that sends every row to experts 0-7: at decode (8 rows) and
    prefill (64 rows) every held expert serves every row routed to it.  A
    capacity-bounded dispatch (at most rows · top_k · 1.25 / n_experts + 1
    per expert) would drop 23 of 64.  Served by the compressed kernels;
    the reference computes in float32 and the kernels' inputs are
    bfloat16, hence the tolerance."""
    cfg = tiny(top_k=8, held=16)
    cm, pruned = _served(cfg)
    ffn = jax.tree.map(lambda a: a[0], pruned["blocks"]["ffn"])
    router = jnp.concatenate([jnp.full((cfg.d_model, 8), 0.05),
                              jnp.full((cfg.d_model, 8), -0.05)], axis=1)
    ffn = dict(ffn, router=router)
    h = jnp.abs(jax.random.normal(jax.random.key(5),
                                  (rows, cfg.d_model))) + 0.1
    extras = jax.tree.map(lambda a: a[0], cm.extras)
    with active_stacked(cm.stacked), L.layer_ctx(extras):
        y, routed = moe_mod.moe_block(h.astype(L.COMPUTE_DTYPE), ffn, cfg)
    np.testing.assert_array_equal(np.asarray(routed),
                                  [rows] * 8 + [0] * 8)
    gates, tope = REF.route(h, router, ref_dims(cfg))
    assert set(np.unique(np.asarray(tope))) == set(range(8))
    want = sum(gates[:, e:e + 1] * (jax.nn.silu(h @ ffn["w_gate"][e])
                                    * (h @ ffn["w_up"][e]))
               @ ffn["w_down"][e] for e in range(8))
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(np.asarray(y, np.float32), np.asarray(want),
                               atol=0.02 * scale)


@pytest.mark.parametrize("first,skewed", [(0, False), (4, False), (0, True)])
def test_grouped_rows_match_every_row_and_drop_none(first, skewed,
                                                    monkeypatch):
    """Past one row tile, dense weights take the grouped path (each routed
    (row, held expert) pair once): the same part and the same routed-row
    counts as every held expert over every row, also when every row goes
    to the same eight experts, and the same gradients.  Both paths round
    each expert's output to bfloat16; the grouped one sums the pairs in
    another order, hence a tolerance of a few bfloat16 steps."""
    cfg = tiny(top_k=8, held=8, first=first)
    p = jax.tree.map(lambda a: a[0], _params(cfg, seed=6)["blocks"]["ffn"])
    if skewed:
        p = dict(p, router=jnp.concatenate(
            [jnp.full((cfg.d_model, 8), 0.05),
             jnp.full((cfg.d_model, 8), -0.05)], axis=1))
    rows = 3 * moe_mod.ROW_TILE_CAP // 2
    h = (jnp.abs(jax.random.normal(jax.random.key(7), (rows, cfg.d_model)))
         + 0.1).astype(L.COMPUTE_DTYPE)

    def part(p, h):
        y, n = moe_mod.moe_block(h, p, cfg)
        return jnp.sum(y.astype(jnp.float32) ** 2), (y, n)
    grad = jax.jit(jax.value_and_grad(part, argnums=(0, 1), has_aux=True))
    (_, (y, n)), (gp, gh) = grad(p, h)
    monkeypatch.setattr(moe_mod, "ROW_TILE_CAP", 10 * rows)
    (_, (y1, n1)), (gp1, gh1) = jax.jit(jax.value_and_grad(
        part, argnums=(0, 1), has_aux=True))(p, h)
    np.testing.assert_array_equal(np.asarray(n), np.asarray(n1))
    if skewed:
        assert np.asarray(n).tolist() == [rows] * 8     # all held by 0-7
    scale = float(jnp.abs(y1.astype(jnp.float32)).max())
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y1, np.float32), atol=0.02 * scale)
    for a, b in [(gh, gh1)] + list(zip(jax.tree.leaves(gp),
                                       jax.tree.leaves(gp1))):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        np.testing.assert_allclose(a, b, atol=0.03 * float(np.abs(b).max()))


def test_grouped_path_runs_only_dense_and_past_a_tile():
    """The compressed kernels take every row, whatever the row count; dense
    weights take the grouped path only past one row tile."""
    cfg = tiny(top_k=8, held=8)
    p = jax.tree.map(lambda a: a[0], _params(cfg)["blocks"]["ffn"])

    def text(rows):
        h = jnp.ones((rows, cfg.d_model), L.COMPUTE_DTYPE)
        return str(jax.make_jaxpr(lambda h: moe_mod.moe_block(h, p, cfg))(h))
    assert "ragged_dot" not in text(moe_mod.ROW_TILE_CAP)
    assert "ragged_dot" in text(moe_mod.ROW_TILE_CAP + 1)
    L.set_proj_hook(lambda x, w, role: None)
    try:
        assert "ragged_dot" not in text(moe_mod.ROW_TILE_CAP + 1)
    finally:
        L.set_proj_hook(None)


# -- the window edge --------------------------------------------------------

def _edge_case(s: int):
    """Keys j carry value one-hot(j) and every score is equal, so the
    output at position i is 1/|visible| on each visible key (to the
    rounding of the bfloat16 weights PV takes) and exactly 0 on the
    others."""
    v = jnp.eye(s, dtype=jnp.float32)[None, :, None, :]       # (1, S, 1, S)
    k = jnp.zeros((1, s, 1, s), jnp.float32)
    return k, v


@pytest.mark.parametrize("i", [WINDOW - 1, WINDOW, WINDOW + 5, 30])
def test_window_edge_in_decode_and_prefill(i):
    s = 32
    k, v = _edge_case(s)
    q = jnp.zeros((1, 1, s), jnp.float32)
    win = jnp.asarray(WINDOW, jnp.int32)
    dec = np.asarray(attn.decode_attention(q, k, v, jnp.array([i + 1]),
                                           win))[0, 0]
    pre = np.asarray(attn.chunked_attention(
        jnp.zeros((1, s, 1, s)), k, v, window=win, q_chunk=8,
        kv_chunk=8))[0, i, 0]
    for out in (dec, pre):
        if i - WINDOW >= 0:
            assert out[i - WINDOW] == 0.0          # key i - W: not seen
        assert out[i - WINDOW + 1 if i >= WINDOW else 0] == pytest.approx(
            1.0 / min(i + 1, WINDOW), rel=2**-8)   # key i - W + 1: seen
        assert out[i] == pytest.approx(1.0 / min(i + 1, WINDOW), rel=2**-8)
        assert out[i + 1:].max(initial=0.0) == 0.0
    # a full layer (window 0) sees every key up to i
    full = np.asarray(attn.decode_attention(q, k, v, jnp.array([i + 1]),
                                            jnp.asarray(0)))[0, 0]
    assert full[0] == pytest.approx(1.0 / (i + 1), rel=2**-8)


# -- RoPE -------------------------------------------------------------------

def test_yarn_frequencies_follow_the_equations():
    """Published sizes: head 128, θ 5e5, factor 16 over 8192 positions,
    β 32 / 1; the ramp runs from dimension pair 18 to 35."""
    yarn = get_config("mellum2-12b-a2.5b").attn_pattern[3].rope
    got = L.rope_inv_freq(yarn, 128)
    i = np.arange(64)
    extrap = 5e5 ** (-2 * i / 128)
    lo = 128 * math.log(8192 / (32 * 2 * math.pi)) / (2 * math.log(5e5))
    hi = 128 * math.log(8192 / (1 * 2 * math.pi)) / (2 * math.log(5e5))
    assert (math.floor(lo), math.ceil(hi)) == (18, 35)
    gamma = 1 - np.clip((i - 18) / (35 - 18), 0, 1)
    np.testing.assert_allclose(got, extrap / 16 * (1 - gamma)
                               + extrap * gamma, rtol=1e-12)
    np.testing.assert_allclose(got[:19], extrap[:19], rtol=1e-12)
    np.testing.assert_allclose(got[35:], extrap[35:] / 16, rtol=1e-12)
    np.testing.assert_allclose(got, REF.inv_freq(
        (5e5, 16.0, 8192, 32.0, 1.0, 1.277), 128), rtol=1e-12)
    assert yarn.attention_factor == pytest.approx(0.1 * math.log(16) + 1)
    np.testing.assert_allclose(
        L.rope_inv_freq(RopeCfg(theta=5e5), 128), extrap, rtol=1e-12)


def test_layer_settings_ride_the_scan_per_layer():
    cfg = dataclasses.replace(get_config("mellum2-12b-a2.5b"), n_layers=8)
    xs = L.layer_attn_xs(cfg)
    assert xs["window"].tolist() == [1024, 1024, 1024, 0] * 2
    np.testing.assert_allclose(np.asarray(xs["rope_scale"]),
                               [1, 1, 1, 1.2772588722239782] * 2, rtol=1e-6)
    assert xs["freqs"].shape == (8, 64)
    assert L.layer_attn_xs(get_config("chatglm3-6b")) is None


# -- the head ---------------------------------------------------------------

def test_untied_head_serves_its_own_table():
    untied = tiny()
    tied = dataclasses.replace(untied, tie_embeddings=True)
    pu = Model(untied).init(jax.random.key(7))
    pt = Model(tied).init(jax.random.key(7))
    assert "head" in pu and "head" not in pt
    assert jax.tree.structure({k: v for k, v in pu.items() if k != "head"}) \
        == jax.tree.structure(pt)
    tokens = jnp.array([[1, 2, 3, 4, 5]])
    lu, cu = Model(untied).prefill(pu, tokens, 8)
    lt, ct = Model(tied).prefill(pt, tokens, 8)
    # the same weights but the head: the hidden states agree, the logits
    # are each table's
    x = Model(tied).hidden_states(pt, tokens, remat=False)
    np.testing.assert_allclose(
        np.asarray(lt), np.asarray(jnp.einsum(
            "btd,vd->btv", x, pt["embed"].astype(L.COMPUTE_DTYPE)),
            np.float32), atol=1e-2)
    np.testing.assert_allclose(
        np.asarray(lu), np.asarray(jnp.einsum(
            "btd,vd->btv", x, pu["head"].astype(L.COMPUTE_DTYPE)),
            np.float32), atol=1e-2)
    # and the head equal to the embedding serves what the tied model does
    same = dict(pu, head=pu["embed"])
    du, _ = Model(untied).decode_step(same, cu, jnp.array([6]),
                                      jnp.array(5))
    dt, _ = Model(tied).decode_step(pt, ct, jnp.array([6]), jnp.array(5))
    np.testing.assert_array_equal(np.asarray(du), np.asarray(dt))
