"""Decode attention reads each KV group once.

``decode_attention`` groups the query heads over the cache's KV heads and
contracts against the cache in its stored (B, S, Hkv, D) layout.  These
tests hold it to an independent float32 NumPy reference that repeats K/V
to every query head, and guard the compiled decode step against building
a (B, S, H, D) copy of the cache."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
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
