"""Optimization flags must preserve semantics (hillclimb changes are
perf-only): decode equivalence under maskedkv, padheads smoke,
sparse FFN path, HLO cost analyzer sanity."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import optflags
from repro.models.transformer import Model


def _decode_logits(cfg, flags, steps=6):
    model = Model(cfg)
    with optflags.optimizations(flags):
        params = model.init(jax.random.key(0))
        cache = model.init_cache(2, 16)
        toks = jnp.array([3, 5], jnp.int32)
        outs = []
        for t in range(steps):
            lg, cache = model.decode_step(params, cache, toks + t,
                                          jnp.asarray(t, jnp.int32))
            outs.append(lg)
    return jnp.stack(outs)


@pytest.mark.parametrize("flag", ["maskedkv"])
def test_decode_flags_preserve_logits(flag):
    cfg = get_config("deepseek-coder-33b").reduced()
    base = _decode_logits(cfg, ())
    opt = _decode_logits(cfg, (flag,))
    np.testing.assert_allclose(np.asarray(base), np.asarray(opt),
                               rtol=2e-2, atol=2e-2)


def test_padheads_trains_and_rounds_heads():
    from repro.models.layers import eff_heads
    with optflags.optimizations(("padheads",)):
        assert eff_heads(56) == 64 and eff_heads(32) == 32
        cfg = get_config("whisper-tiny").reduced()
        model = Model(cfg)
        params = model.init(jax.random.key(0))
        batch = {
            "tokens": jnp.zeros((2, 8), jnp.int32),
            "labels": jnp.zeros((2, 8), jnp.int32),
            "enc_frames": jnp.zeros((2, cfg.enc_seq, cfg.d_model)),
        }
        loss = model.loss(params, batch)
        assert jnp.isfinite(loss)
    assert eff_heads(56) == 56          # flag off outside the context


def test_sparseffn_decode_runs():
    cfg = get_config("deepseek-coder-33b").reduced()
    with optflags.optimizations(("sparseffn",)):
        model = Model(cfg)
        params = model.init(jax.random.key(1))
        assert "payload_gate" in jax.tree.leaves(
            {"k": list(params["blocks"]["ffn"].keys())})[0] or \
            "payload_gate" in params["blocks"]["ffn"]
        cache = model.init_cache(2, 8)
        lg, _ = model.decode_step(params, cache, jnp.array([1, 2], jnp.int32),
                                  jnp.asarray(0, jnp.int32))
        assert jnp.all(jnp.isfinite(lg))


def test_unknown_flag_rejected():
    with pytest.raises(ValueError):
        with optflags.optimizations(("nonsense",)):
            pass


def test_hlo_cost_trip_counts():
    """The analyzer must multiply while bodies by known trip counts."""
    from repro.launch import hlo_cost

    def scanned(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        c, _ = jax.lax.scan(body, x, None, length=8)
        return c

    x = jnp.zeros((64, 64))
    c = jax.jit(scanned).lower(x, x).compile()
    res = hlo_cost.analyze_compiled(c)
    assert res["flops"] == pytest.approx(8 * 2 * 64 ** 3, rel=0.01)
