"""Per-architecture smoke tests: REDUCED configs, one forward/train step and
one decode step on CPU, asserting output shapes and finite values."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SHAPES, get_config, list_archs
from repro.models.transformer import Model, input_specs

# full end-to-end / many-model sweeps dominate suite wall-clock
pytestmark = pytest.mark.slow

ARCHS = list_archs()


def _batch(cfg, b=2, s=32, rng=None):
    rng = rng or np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, (b, s)), jnp.int32)
    batch = {"tokens": tokens,
             "labels": jnp.asarray(rng.integers(0, cfg.vocab, (b, s)),
                                   jnp.int32)}
    if cfg.family == "encdec":
        batch["enc_frames"] = jnp.asarray(
            rng.normal(size=(b, cfg.enc_seq, cfg.d_model)), jnp.float32)
    return batch


def test_all_archs_registered():
    assert len(ARCHS) == 11


@pytest.mark.parametrize("arch", ARCHS)
def test_full_config_params_count(arch):
    cfg = get_config(arch)
    n = cfg.params_count()
    assert n > 1e8 or arch == "whisper-tiny"
    assert cfg.active_params_count() <= n + 1


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_train_step(arch):
    """Reduced config: one loss+grad step, finite outputs."""
    cfg = get_config(arch).reduced()
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    batch = _batch(cfg)

    loss, grads = jax.jit(jax.value_and_grad(model.loss))(params, batch)
    assert jnp.isfinite(loss), f"{arch}: non-finite loss"
    leaves = jax.tree.leaves(grads)
    assert leaves, "no grads"
    for g in leaves:
        assert jnp.all(jnp.isfinite(g)), f"{arch}: non-finite grad"


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_decode_step(arch):
    cfg = get_config(arch).reduced()
    model = Model(cfg)
    params = model.init(jax.random.key(1))
    b, max_len = 2, 64
    cache = model.init_cache(b, max_len)
    step = jax.jit(model.decode_step)
    tokens = jnp.array([1, 2], jnp.int32)
    logits, cache = step(params, cache, tokens, jnp.asarray(0, jnp.int32))
    assert logits.shape == (b, cfg.vocab)
    assert jnp.all(jnp.isfinite(logits)), f"{arch}: non-finite logits"
    logits2, cache = step(params, cache, tokens + 1, jnp.asarray(1, jnp.int32))
    assert not jnp.allclose(logits, logits2), "decode ignores position/cache"


@pytest.mark.parametrize("arch", ARCHS)
def test_input_specs_all_shapes(arch):
    cfg = get_config(arch)
    for shape in SHAPES.values():
        specs = input_specs(cfg, shape)
        assert "tokens" in specs
        if shape.kind == "train":
            assert specs["tokens"].shape == (shape.global_batch, shape.seq_len)
        if shape.kind == "decode":
            assert specs["tokens"].shape == (shape.global_batch,)


def test_decode_matches_forward_logits_dense():
    """Decoding token-by-token must agree with the parallel forward pass
    (teacher forcing) for a uniform dense arch."""
    cfg = get_config("deepseek-coder-33b").reduced()
    model = Model(cfg)
    params = model.init(jax.random.key(2))
    rng = np.random.default_rng(3)
    b, s = 2, 8
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, (b, s)), jnp.int32)

    # parallel hidden states → logits at each position
    hs = model.hidden_states(params, tokens, remat=False)
    from repro.models.layers import logits_head
    want = jax.vmap(lambda t: logits_head(hs[:, t], params["embed"]),
                    out_axes=1)(jnp.arange(s))

    cache = model.init_cache(b, s)
    outs = []
    for t in range(s):
        lg, cache = model.decode_step(params, cache, tokens[:, t],
                                      jnp.asarray(t, jnp.int32))
        outs.append(lg)
    got = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0.15, atol=0.15)


def test_ssm_decode_matches_forward():
    """Mamba2: chunked SSD scan ≡ step-by-step recurrence."""
    cfg = get_config("mamba2-780m").reduced()
    model = Model(cfg)
    params = model.init(jax.random.key(4))
    rng = np.random.default_rng(5)
    b, s = 2, 16
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, (b, s)), jnp.int32)
    hs = model.hidden_states(params, tokens, remat=False)
    from repro.models.layers import logits_head
    want = logits_head(hs[:, -1], params["embed"])
    cache = model.init_cache(b, s)
    for t in range(s):
        got, cache = model.decode_step(params, cache, tokens[:, t],
                                       jnp.asarray(t, jnp.int32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0.2, atol=0.2)
