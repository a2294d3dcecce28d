"""The float32 reference agrees with the program's dense forward."""

import dataclasses

import jax
import numpy as np
import pytest

import spec
import weights
from reference import gqa

from repro.configs import get_config
from repro.models.transformer import Model

# chatglm3-6b's partial RoPE and GQA, deepseek-coder-33b's full RoPE and
# base, at widths a CPU test holds
SMALL = {
    "chatglm3-6b": dict(n_layers=2, d_model=256, n_heads=4, n_kv_heads=2,
                        head_dim=64, d_ff=512, vocab=512, rope_fraction=0.5,
                        rope_base=10000.0, norm_eps=1e-5),
    "deepseek-coder-33b": dict(n_layers=2, d_model=256, n_heads=8,
                               n_kv_heads=2, head_dim=32, d_ff=384,
                               vocab=384, rope_fraction=1.0,
                               rope_base=100000.0, norm_eps=1e-6),
}

#: The program runs bfloat16 activations (8 significant bits) over the same
#: weights; at these widths its logits land within 1-2% of the largest
#: reference logit, while a wrong rotation, mask or norm moves them by
#: their own scale.
TOL = 4e-2

GQA = spec.arch("gqa")


def _program_logits(arch, dims, w, tokens):
    cfg = dataclasses.replace(
        get_config(arch), n_layers=dims["n_layers"], d_model=dims["d_model"],
        n_heads=dims["n_heads"], n_kv_heads=dims["n_kv_heads"],
        d_head=dims["head_dim"], d_ff=dims["d_ff"], vocab=dims["vocab"],
        rope_fraction=dims["rope_fraction"], rope_base=dims["rope_base"],
        norm_eps=dims["norm_eps"])
    logits, _ = Model(cfg).prefill(GQA.program_tree(w), tokens[None],
                                   max_len=tokens.shape[0])
    return np.asarray(logits[0])


def _err(got, want):
    return float(np.abs(got - want).max() / np.abs(want).max())


@pytest.mark.parametrize("arch", sorted(SMALL))
def test_reference_matches_dense_model(arch):
    dims = SMALL[arch]
    masks = {r: (64, 64) for r in GQA.ROLES}
    w = GQA.make(7, dims, masks, 0.5)
    tokens = np.random.default_rng(0).integers(0, dims["vocab"], 96)
    rows = np.arange(96)
    ref = np.asarray(gqa.logits(w, tokens, rows, dims))
    prog = _program_logits(arch, dims, w, tokens)
    assert _err(prog, ref) < TOL
    # the comparison sees a wrong rotation
    other = dict(dims, rope_fraction=1.5 - dims["rope_fraction"])
    assert _err(np.asarray(gqa.logits(w, tokens, rows, other)), ref) > 5 * TOL


def test_padding_does_not_reach_earlier_positions():
    dims = SMALL["chatglm3-6b"]
    masks = {r: (64, 64) for r in GQA.ROLES}
    w = GQA.make(3, dims, masks, 0.5)
    tokens = np.random.default_rng(1).integers(0, dims["vocab"], 100)
    rows = np.arange(100)
    a = np.asarray(gqa.logits(w, tokens, rows, dims, bucket=256))
    b = np.asarray(gqa.logits(w, tokens, rows, dims, bucket=100))
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def test_weights_are_sparse_at_the_served_blocks():
    dims = SMALL["chatglm3-6b"]
    masks = {r: (128, 64) for r in GQA.ROLES}
    masks["attn.wq"] = (256, 32)
    w = GQA.make(11, dims, masks, 0.5)
    up = np.asarray(w["w_up"][0]).reshape(2, 128, 8, 64)
    nonzero = (np.abs(up).sum(axis=(1, 3)) > 0)
    assert nonzero.sum() == 8                   # exactly half of 16 blocks
    q = np.asarray(w["wq"][1]).reshape(1, 256, 8, 32)
    assert (np.abs(q).sum(axis=(1, 3)) > 0).sum() == 4
    nnz = weights.nnz_per_layer(GQA.roles(dims), masks, 0.5)
    assert nnz["ffn.w_up"] == int((np.asarray(w["w_up"][0]) != 0).sum())
    assert nnz["attn.wq"] == int((np.asarray(w["wq"][1]) != 0).sum())
    again = GQA.make(11, dims, masks, 0.5)
    assert all(np.array_equal(np.asarray(again[k]), np.asarray(w[k]))
               for k in w)
    assert not np.array_equal(np.asarray(GQA.make(12, dims, masks, 0.5)
                                         ["w_up"]), np.asarray(w["w_up"]))
    jax.clear_caches()


def test_mask_blocks_come_from_the_configuration():
    dims = SMALL["chatglm3-6b"]
    roles = GQA.roles(dims)
    blocks = {r: [128, 64] for r in roles}
    cfg = {"sparsity": {"mask_blocks": blocks}}
    assert weights.masks(cfg, roles) == {r: (128, 64) for r in roles}
    blocks["ffn.w_down"] = [96, 64]                   # does not tile 512
    with pytest.raises(ValueError, match="ffn.w_down"):
        weights.masks(cfg, roles)
