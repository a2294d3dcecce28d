"""Decode attention reads each KV group once, and the cache in place.

``decode_attention`` groups the query heads over the cache's KV heads and
contracts against the cache in its stored (B, S, Hkv, D) layout.  These
tests hold it to an independent float32 NumPy reference that repeats K/V
to every query head, and guard the compiled decode step against building
a (B, S, H, D) copy of the cache.  The decode step attends to the cache
plus the new token and writes every layer's new K/V after its layer scan:
it is held to a plain step that writes first and attends after, and
guarded against blending a layer's cache or carrying the stacked cache
through the scan."""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
from jax.extend import core as jex
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import layers as L
from repro.models import moe as moe_mod
from repro.models.attention import decode_attention
from repro.models.transformer import Model

B, S, D = 4, 24, 32


def _reference(q, k, v, lengths):
    """float32 attention with K/V repeated to all H heads, slot by slot."""
    h, hk = q.shape[1], k.shape[2]
    k = np.repeat(k, h // hk, axis=2)                    # (B, S, H, D)
    v = np.repeat(v, h // hk, axis=2)
    out = np.zeros(q.shape, np.float32)
    for i, n in enumerate(lengths):
        s = np.einsum("hd,shd->hs", q[i], k[i, :n]) / np.sqrt(q.shape[-1])
        w = np.exp(s - s.max(axis=-1, keepdims=True))
        w /= w.sum(axis=-1, keepdims=True)
        out[i] = np.einsum("hs,shd->hd", w, v[i, :n])
    return out


def _bf16(rng, shape):
    """Normal draws rounded to bfloat16, and their exact float32 values."""
    x = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    return x, np.asarray(x, np.float32)


@pytest.mark.parametrize("per_slot", [False, True],
                         ids=["scalar_length", "per_slot_lengths"])
@pytest.mark.parametrize("h,hk", [(4, 4), (8, 2), (14, 2), (32, 2)])
def test_decode_attention_matches_repeated_kv_reference(h, hk, per_slot):
    rng = np.random.default_rng(h * 100 + hk)
    q, q32 = _bf16(rng, (B, h, D))
    k, k32 = _bf16(rng, (B, S, hk, D))
    v, v32 = _bf16(rng, (B, S, hk, D))
    if per_slot:
        lengths = [1, S, 7, 13]                 # one position and the whole
        length = jnp.asarray(lengths, jnp.int32)
    else:
        lengths = [11] * B
        length = jnp.asarray(11, jnp.int32)
    out = jax.jit(decode_attention)(q, k, v, length)
    assert out.shape == (B, h, D) and out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               _reference(q32, k32, v32, lengths),
                               rtol=2e-2, atol=2e-2)


def test_decode_attention_refuses_heads_that_do_not_group():
    q = jnp.zeros((1, 6, D), jnp.bfloat16)
    kv = jnp.zeros((1, S, 4, D), jnp.bfloat16)
    with pytest.raises(ValueError, match="do not group"):
        decode_attention(q, kv, kv, S)


def test_decode_step_builds_no_cache_copy_per_query_head():
    """The lowered and the compiled decode step of a GQA model (r > 1,
    per-slot positions) hold no (B, S, H, D) array: the cache is never
    repeated to every query head."""
    cfg = get_config("chatglm3-6b").reduced()
    h, hk, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    assert h // hk > 1
    b, s = 3, 40
    model = Model(cfg)
    params = jax.eval_shape(model.init, jax.random.key(0))
    cache = jax.eval_shape(lambda: model.init_cache(b, s))
    tokens = jax.ShapeDtypeStruct((b,), jnp.int32)
    lowered = jax.jit(model.decode_step).lower(params, cache, tokens, tokens)
    repeated = re.compile(rf"\b{b}x{s}x{h}x{d}x|\[{b},{s},{h},{d}\]"
                          rf"|\b{b}x{s}x{hk}x{h // hk}x{d}x"
                          rf"|\[{b},{s},{hk},{h // hk},{d}\]")
    stored = re.compile(rf"\b{b}x{s}x{hk}x{d}x|\[{b},{s},{hk},{d}\]")
    for text in (lowered.as_text(), lowered.compile().as_text()):
        assert stored.search(text), "the cache's own shape is not in the step"
        assert not repeated.search(text), repeated.search(text).group(0)


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                if isinstance(sub, jex.ClosedJaxpr):
                    yield from _eqns(sub.jaxpr)
                elif isinstance(sub, jex.Jaxpr):
                    yield from _eqns(sub)


def test_decode_step_writes_the_cache_once_after_the_scan():
    """The lowered decode step of a GQA model with per-slot positions
    blends no layer cache: no select of a (B, S, Hkv, D) cache slice.  The
    layer-stacked (L, B, S, Hkv, D) caches ride no layer scan, neither in
    nor out nor carried, and each is written by exactly one scatter."""
    cfg = get_config("chatglm3-6b").reduced()
    hk, d = cfg.n_kv_heads, cfg.head_dim
    b, s = 3, 40
    model = Model(cfg)
    params = jax.eval_shape(model.init, jax.random.key(0))
    cache = jax.eval_shape(lambda: model.init_cache(b, s))
    tokens = jax.ShapeDtypeStruct((b,), jnp.int32)
    layer, stacked = (b, s, hk, d), (cfg.n_layers, b, s, hk, d)
    assert cache["self"]["k"].shape == stacked

    text = jax.jit(model.decode_step).lower(params, cache, tokens,
                                            tokens).as_text()
    shape = f"tensor<{b}x{s}x{hk}x{d}x"
    selects = [ln for ln in text.splitlines() if "stablehlo.select" in ln]
    assert selects and not [ln for ln in selects if shape in ln]

    jaxpr = jax.make_jaxpr(model.decode_step)(params, cache, tokens, tokens)
    eqns = list(_eqns(jaxpr.jaxpr))
    assert not [e for e in eqns if e.primitive.name == "select_n"
                and e.outvars[0].aval.shape in (layer, stacked)]
    writes = [e for e in eqns if e.primitive.name == "scatter"
              and e.outvars[0].aval.shape == stacked]
    assert len(writes) == 2
    scans = [e for e in eqns if e.primitive.name == "scan"]
    assert scans
    for e in scans:
        ridden = e.invars[e.params["num_consts"]:] + e.outvars
        assert stacked not in [v.aval.shape for v in ridden]


# -- the decode step against the write-then-attend order ---------------------

def _write_then_attend(model, params, cache, tokens, pos):
    """A plain decode step: layer by layer, write the new token's K/V into
    the cache at its position first, then attend over the written cache.
    Returns (logits, k, v) with k / v the written (L, B, S, Hkv, D) caches."""
    cfg = model.cfg
    b = tokens.shape[0]
    x = jnp.take(params["embed"], tokens, axis=0).astype(L.COMPUTE_DTYPE)
    lxs = L.layer_attn_xs(cfg)
    k_all, v_all = cache["self"]["k"], cache["self"]["v"]
    s = k_all.shape[2]
    at = pos % s if cfg.window else pos
    rows = jnp.arange(b)
    nh, nk, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks, vs = [], []
    for i in range(cfg.n_layers):
        p = jax.tree.map(lambda a: a[i], params["blocks"])
        if lxs is None:
            freqs, scale, window = L.rope_freqs(cfg), None, None
        else:
            freqs, scale, window = (lxs["freqs"][i], lxs["rope_scale"][i],
                                    lxs["window"][i])
        h = L.rms_norm(x[:, None], p["ln1"], cfg.norm_eps)[:, 0]
        q = L.apply_rope(L.proj(h, p["attn"]["wq"], "attn.wq")
                         .reshape(b, 1, nh, hd), pos[:, None], freqs,
                         scale)[:, 0]
        k = L.apply_rope(L.proj(h, p["attn"]["wk"], "attn.wk")
                         .reshape(b, 1, nk, hd), pos[:, None], freqs,
                         scale)[:, 0]
        v = L.proj(h, p["attn"]["wv"], "attn.wv").reshape(b, nk, hd)
        kc = k_all[i].at[rows, at].set(k.astype(k_all.dtype), mode="drop")
        vc = v_all[i].at[rows, at].set(v.astype(v_all.dtype), mode="drop")
        o = decode_attention(q, kc, vc, jnp.minimum(pos + 1, s), window)
        x = x + L.proj(o.reshape(b, nh * hd), p["attn"]["wo"], "attn.wo")
        h = L.rms_norm(x[:, None], p["ln2"], cfg.norm_eps)[:, 0]
        if cfg.moe:
            x = x + moe_mod.moe_block(h, p["ffn"], cfg)[0]
        else:
            x = x + L.mlp(h[:, None], p["ffn"])[:, 0]
        ks.append(kc)
        vs.append(vc)
    x = L.rms_norm(x[:, None], params["final_norm"], cfg.norm_eps)[:, 0]
    return L.logits_head(x, L.head_table(params)), jnp.stack(ks), \
        jnp.stack(vs)


def _mellum_windows(window):
    """The reduced mellum2-12b-a2.5b at 8 layers (two periods of three
    windowed layers and one full), each window ``window``."""
    cfg = get_config("mellum2-12b-a2.5b").reduced()
    pat = tuple(dataclasses.replace(k, window=window if k.window else 0)
                for k in cfg.attn_pattern)
    return dataclasses.replace(cfg, n_layers=8, attn_pattern=pat)


S_STEP = 16
# per-slot positions of three consecutive steps: a free slot riding at 0,
# one crossing the 8-key window, one reaching S - 1, one early
FULL_POS = [[0, 6, S_STEP - 3, 2], [0, 7, S_STEP - 2, 3],
            [0, 8, S_STEP - 1, 4]]
# a ring cache (a stack-wide window of S positions) past its length
RING_POS = [[0, S_STEP - 1, 2 * S_STEP + 5, 9],
            [0, S_STEP, 2 * S_STEP + 6, 10],
            [0, S_STEP + 1, 2 * S_STEP + 7, 11]]


@pytest.mark.parametrize("name", ["chatglm3-6b", "mellum2-12b-a2.5b",
                                  "chatglm3-6b-ring"])
def test_decode_steps_match_write_then_attend(name):
    """Several decode steps, each row at its own position, give the logits
    and caches of a step that writes each layer's token first and attends
    after: attending to the cache (its slot at the write position hidden)
    plus the new token is the same attention.  The caches start filled
    with noise, so a stale entry at the write position that leaked into
    the softmax would show.

    Equal to the bit: the new token's score and weight take the column of
    the slot it overwrites, so the softmax sums the same terms in the same
    order as over the written cache, and the attention output is rounded
    to bfloat16 once, as there.  A stale entry left visible, or the new
    token left out, moves every step's logits by 0.5-4.4."""
    if name == "mellum2-12b-a2.5b":
        cfg, steps = _mellum_windows(8), FULL_POS
    else:
        cfg = get_config("chatglm3-6b").reduced()
        steps = FULL_POS
        if name.endswith("ring"):
            cfg, steps = dataclasses.replace(cfg, window=S_STEP), RING_POS
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    b = len(steps[0])
    cache = model.init_cache(b, S_STEP)
    rng = np.random.default_rng(1)
    for n in ("k", "v"):
        cache["self"][n] = jnp.asarray(
            rng.standard_normal(cache["self"][n].shape), jnp.bfloat16)
    ref = {"self": dict(cache["self"])}
    step = jax.jit(model.decode_step)
    plain = jax.jit(functools.partial(_write_then_attend, model))
    tokens = jnp.asarray([5, 17, 29, 41], jnp.int32)
    for t, pos in enumerate(steps):
        pos = jnp.asarray(pos, jnp.int32)
        logits, cache = step(params, cache, tokens + t, pos)
        want, k, v = plain(params, ref, tokens + t, pos)
        ref = {"self": {"k": k, "v": v}}
        np.testing.assert_array_equal(np.asarray(logits), np.asarray(want))
    for n in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(cache["self"][n], np.float32),
                                      np.asarray(ref["self"][n], np.float32))
