"""The routed-experts architecture (``arch/moe.py``) and its reference
(``reference/moe.py``): dims and roles from the configuration, masks that
tile the published widths, seeded weights, the counts of a traced run, the
reference against a forward written out by hand, and a tiny cell of it run
through the harness on the CPU, whose controls read not correct."""

import json
import math
import os
import shutil
from types import SimpleNamespace as NS

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

import counts
import run
import spec
import tracing
import weights

MOE = spec.arch("moe")
CFG = spec.config("mellum2-12b-a2.5b")

#: Blocks the plan serves at the tiny widths below.
TINY_MASKS = {"attn.wq": [128, 256], "attn.wk": [128, 128],
              "attn.wv": [128, 128], "attn.wo": [128, 256],
              "moe.w_gate": [128, 128], "moe.w_up": [128, 128],
              "moe.w_down": [128, 256]}


def tiny_cfg() -> dict:
    """The configuration cut to d_model 256, 4 heads of 64 over 2 groups,
    16 experts of width 128 (4 held, top 8), a 512-row vocabulary and a
    16-key window; everything else as the file has it."""
    cfg = spec.config("mellum2-12b-a2.5b")
    cfg.update({"hidden_size": 256, "head_dim": 64, "num_attention_heads": 4,
                "num_key_value_heads": 2, "moe_intermediate_size": 128,
                "num_experts": 4, "num_experts_per_tok": 8,
                "vocab_size": 512, "sliding_window": 16})
    cfg["dims"] = dict(cfg["dims"], n_experts=16)
    cfg["sparsity"] = dict(cfg["sparsity"], mask_blocks=TINY_MASKS)
    # on four seeds the program read 0.004-0.094 here, the fp8 control
    # 0.28-0.46
    cfg["limits"] = {"logit_gap": 0.2}
    return cfg


@pytest.fixture
def moe_bench(tmp_path):
    """A benchmark directory whose mellum cell serves the tiny cut, on 4
    slots of 96 positions."""
    for sub in ("configs", "traffic"):
        (tmp_path / sub).mkdir()
    (tmp_path / "configs" / "tiny-moe.json").write_text(json.dumps(tiny_cfg()))
    mix = spec.traffic("decode-4k")
    mix.update({"slots": 4, "max_len": 96, "pool": 64})
    mix["prompt"].update({"median": 32, "min": 16, "max": 48, "round_to": 16})
    mix["output"].update({"median": 16, "min": 8, "max": 40})
    (tmp_path / "traffic" / "decode-4k.json").write_text(json.dumps(mix))
    for sub in ("arch", "metrics"):
        shutil.copytree(os.path.join(spec.HERE, sub), tmp_path / sub,
                        ignore=shutil.ignore_patterns("__pycache__"))
    bench = spec.benchmark()
    for w in bench["workloads"]:
        if w["config"] == "mellum2-12b-a2.5b":
            w["config"] = "tiny-moe"
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp_path


CELL = "mellum2-12b-a2.5b.decode-4k"


# -- dims, roles, masks -------------------------------------------------------

def test_dims_and_fanout_roles_at_published_widths():
    d = MOE.dims(CFG)
    assert (d["n_layers"], d["d_model"], d["n_heads"], d["n_kv_heads"],
            d["head_dim"], d["d_expert"]) == (4, 2304, 32, 4, 128, 896)
    assert (d["n_experts"], d["n_held"], d["first_expert"], d["top_k"]) == \
        (64, 16, 0, 8)
    assert d["vocab"] == 24576 and d["norm_topk_prob"] is True
    assert d["activations"] == "bfloat16"      # what the reference rounds to
    assert d["windows"] == (1024, 1024, 1024, 0)
    assert d["rope"][0] == (500000.0, 1.0, 0, 0.0, 0.0, 1.0)
    assert d["rope"][3] == (500000.0, 16.0, 8192, 32.0, 1.0,
                            1.2772588722239782)
    assert MOE.roles(d) == {
        "attn.wq": (2304, 4096, 1), "attn.wk": (2304, 512, 1),
        "attn.wv": (2304, 512, 1), "attn.wo": (4096, 2304, 1),
        "moe.w_gate": (2304, 896, 16), "moe.w_up": (2304, 896, 16),
        "moe.w_down": (896, 2304, 16)}
    # the cuts are listed, beside the published values
    assert CFG["published"] == {"num_hidden_layers": 28, "num_experts": 64,
                                "vocab_size": 98304}
    assert len(CFG["layer_types"]) == 28


def test_masks_tile_the_widths_and_are_the_blocks_the_plan_serves():
    d = MOE.dims(CFG)
    roles = MOE.roles(d)
    masks = weights.masks(CFG, roles)              # raises where one does not
    plan = run.build_plan(MOE.program_config(CFG, d), CFG)
    assert {op.role: (op.choice.block_n, op.choice.block_k)
            for op in plan.ops} == masks
    assert {op.role for op in plan.ops} == set(roles)
    assert all(op.choice.kind == "bitmap" for op in plan.ops)
    nnzb = weights.nnz_blocks(roles, masks, 0.5)
    # floor(half) of each role's blocks at the served block grid, per held
    # expert: 6 of 12 (wq, wo), 1 of 3 (wk, wv, w_gate, w_up), 3 of 7
    # (w_down)
    assert nnzb == {"attn.wq": 6, "attn.wk": 1, "attn.wv": 1, "attn.wo": 6,
                    "moe.w_gate": 16, "moe.w_up": 16, "moe.w_down": 48}
    with pytest.raises(ValueError, match="does not tile"):
        weights.masks(dict(CFG, sparsity=dict(
            CFG["sparsity"], mask_blocks=dict(
                CFG["sparsity"]["mask_blocks"], **{"moe.w_down": [256, 2304]}))),
            roles)


def test_seeded_weights_are_deterministic_and_sparse_per_expert():
    cfg = tiny_cfg()
    d = MOE.dims(cfg)
    roles = MOE.roles(d)
    masks = weights.masks(cfg, roles)
    a = MOE.make(2**31 + 5, d, masks, 0.5)
    b = MOE.make(2**31 + 5, d, masks, 0.5)
    c = MOE.make(2**31 + 6, d, masks, 0.5)
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))
    assert not np.array_equal(np.asarray(a["w_gate"]), np.asarray(c["w_gate"]))
    assert a["w_gate"].shape == (4, 4, 256, 128)
    assert a["router"].shape == (4, 256, 16)
    assert a["head"].shape == a["embed"].shape == (512, 256)
    bn, bk = masks["moe.w_gate"]
    blocks = np.asarray(a["w_gate"]).reshape(4, 4, 256 // bn, bn, 128 // bk,
                                             bk)
    kept = (np.abs(blocks).sum((3, 5)) > 0).sum((2, 3))
    np.testing.assert_array_equal(kept, 1)        # 1 of 2 in every expert
    tree = MOE.program_tree(a)
    assert set(tree["blocks"]["ffn"]) == {"router", "w_gate", "w_up",
                                          "w_down"}
    assert tree["head"] is a["head"]


# -- the counts ---------------------------------------------------------------

def _kernel_roles():
    """Two kernel roles of a 2-layer stack: an attention role and an
    expert role of 4 held experts."""
    return [counts.KernelRole("attn.wq", 256, 256, 128, 256, 1, 4, 0),
            counts.KernelRole("moe.w_gate", 256, 128, 128, 128, 4, 4, 0)]


SMALL = {"n_layers": 2, "d_model": 256, "n_heads": 4, "head_dim": 64,
         "n_experts": 16, "n_held": 4, "top_k": 4, "vocab": 512,
         "windows": (16, 0)}


def test_decode_flops_count_routed_experts_and_windows():
    ctx = {"dims": SMALL, "kernel_roles": _kernel_roles(),
           "rec": NS(ctx=[10, 40])}
    # per layer: 2·(128·256) attention, 2·(4·128·128)/4 · (4·4/16) experts,
    # router 2·256·16; per token the head 2·512·256; keys: min(c, 16) on
    # layer 0 and c on layer 1, 4·4·64 FLOPs each
    per_layer = 2 * 128 * 256 + 2 * 128 * 128 * 1.0 + 2 * 256 * 16
    per_token = 2 * per_layer + 2 * 512 * 256
    keys = (10 + 16) + (10 + 40)
    assert MOE.decode_flops(ctx) == 2 * per_token + 4 * 4 * 64 * keys
    assert MOE.routed_experts(MOE.dims(CFG)) == 2.0


def test_decode_kv_bytes_read_each_layer_over_its_window():
    ctx = {"dims": SMALL, "kv_bytes_per_position": 2 * 1024.0,
           "rec": NS(ctx=[10, 40])}
    assert MOE.decode_kv_bytes(ctx) == 1024.0 * ((10 + 16) + (10 + 40))


def test_step_weight_bytes_hold_every_held_expert_router_and_head():
    roles = _kernel_roles()
    stacked = NS(roles={
        "attn.wq": NS(data={"row_ids": jnp.zeros((2, 1), jnp.int32),
                            "counts": jnp.zeros((2, 1), jnp.int32),
                            "offsets": jnp.zeros((2, 1), jnp.int32)}),
        "moe.w_gate": NS(data={"row_ids": jnp.zeros((2, 4, 1), jnp.int32),
                               "counts": jnp.zeros((2, 4, 1), jnp.int32),
                               "offsets": jnp.zeros((2, 4, 1), jnp.int32)})})
    params = {"head": jnp.zeros((512, 256), jnp.float32),
              "blocks": {"ffn": {"router": jnp.zeros((2, 256, 16),
                                                     jnp.float32)}}}
    ctx = {"dims": SMALL, "kernel_roles": roles, "stacked": stacked,
           "params": params}
    want = 2 * 1 * (128 * 256 * 4 + 4) + 2 * 2 * 4 \
        + 2 * 4 * (128 * 128 * 4 + 4) + 2 * 2 * 4 * 4 \
        + 2 * 256 * 16 * 4 + 512 * 256 * 4
    assert MOE.step_weight_bytes(ctx) == want
    del stacked.roles["moe.w_gate"]
    stacked.roles["moe.w_up"] = NS(data=None)
    with pytest.raises(ValueError, match="moe.w_up"):
        MOE.step_weight_bytes(ctx)


# -- the reference, against a forward written out by hand --------------------

def _bf16(v):
    return np.asarray(v, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float64)


def _by_hand(w, tokens, d, act=lambda v: v):
    """Two layers, one sequence, written out in float64 loops over
    positions, heads, keys and experts; ``act`` rounds each activation the
    layer hands on."""
    w = {k: np.asarray(v, np.float64) for k, v in w.items()}
    x = act(w["embed"][tokens])
    s, hd = len(tokens), d["head_dim"]
    r = d["n_heads"] // d["n_kv_heads"]

    def rms(v, g):
        return v / np.sqrt((v * v).mean(-1, keepdims=True) + d["norm_eps"]) \
            * (1 + g)

    def rope(v, pos, rp):
        theta, factor, orig, bf, bs, att = rp
        i = np.arange(hd // 2)
        f = theta ** (-2.0 * i / hd)
        if factor > 1:
            lo = math.floor(hd * math.log(orig / (bf * 2 * math.pi))
                            / (2 * math.log(theta)))
            hi = math.ceil(hd * math.log(orig / (bs * 2 * math.pi))
                           / (2 * math.log(theta)))
            g = 1 - np.clip((i - lo) / (hi - lo), 0, 1)
            f = f / factor * (1 - g) + f * g
        out = v.copy()
        for j in range(hd // 2):
            c, sn = math.cos(pos * f[j]) * att, math.sin(pos * f[j]) * att
            a, b = v[..., 2 * j], v[..., 2 * j + 1]
            out[..., 2 * j], out[..., 2 * j + 1] = a * c - b * sn, a * sn + b * c
        return out

    for layer in range(d["n_layers"]):
        lw = {k: w[k][layer] for k in ("ln1", "ln2", "wq", "wk", "wv", "wo",
                                       "router", "w_gate", "w_up", "w_down")}
        win, rp = d["windows"][layer], d["rope"][layer]
        h = act(rms(x, lw["ln1"]))
        q = act(h @ lw["wq"]).reshape(s, d["n_heads"], hd)
        k = act(h @ lw["wk"]).reshape(s, d["n_kv_heads"], hd)
        v = act(h @ lw["wv"]).reshape(s, d["n_kv_heads"], hd)
        q = act(np.stack([rope(q[t], t, rp) for t in range(s)]))
        k = act(np.stack([rope(k[t], t, rp) for t in range(s)]))
        a = np.zeros((s, d["n_heads"], hd))
        for t in range(s):
            keys = [j for j in range(t + 1) if not win or j > t - win]
            for hh in range(d["n_heads"]):
                sc = act(act(np.array([q[t, hh] @ k[j, hh // r]
                                       for j in keys]))
                         * act(1 / math.sqrt(hd)))
                p = np.exp(sc - sc.max())
                p = act(p / p.sum())
                a[t, hh] = sum(pj * v[j, hh // r] for pj, j in zip(p, keys))
        x = act(x + act(act(a).reshape(s, -1) @ lw["wo"]))
        h = act(rms(x, lw["ln2"]))
        y = np.zeros_like(x)
        for t in range(s):
            z = h[t] @ lw["router"]
            p = np.exp(z - z.max())
            p /= p.sum()
            top = np.argsort(-p)[: d["top_k"]]
            g = p[top] / p[top].sum()
            for e, ge in zip(top, g):
                j = e - d["first_expert"]
                if 0 <= j < d["n_held"]:
                    f = act(h[t] @ lw["w_gate"][j])
                    f = act(act(f / (1 + np.exp(-f)))
                            * act(h[t] @ lw["w_up"][j]))
                    y[t] = y[t] + ge * act(f @ lw["w_down"][j])
        x = act(x + act(y))
    return act(rms(x, w["final_norm"])) @ w["head"].T


def test_reference_matches_a_forward_written_by_hand():
    d = {"n_layers": 2, "d_model": 16, "n_heads": 4, "n_kv_heads": 2,
         "head_dim": 8, "n_experts": 8, "n_held": 3, "first_expert": 2,
         "top_k": 3, "norm_topk_prob": True, "vocab": 32, "norm_eps": 1e-6,
         "windows": (3, 0),
         "rope": ((100.0, 1.0, 0, 0.0, 0.0, 1.0),
                  (100.0, 4.0, 64, 4.0, 1.0, 1.1))}
    keys = iter(jax.random.split(jax.random.key(3), 13))

    def rnd(*shape, scale=1.0):
        return scale * jax.random.normal(next(keys), shape)
    w = {"embed": rnd(32, 16), "head": rnd(32, 16, scale=0.25),
         "final_norm": rnd(16, scale=0.1), "ln1": rnd(2, 16, scale=0.1),
         "ln2": rnd(2, 16, scale=0.1), "wq": rnd(2, 16, 32, scale=0.25),
         "wk": rnd(2, 16, 16, scale=0.25), "wv": rnd(2, 16, 16, scale=0.25),
         "wo": rnd(2, 32, 16, scale=0.2), "router": rnd(2, 16, 8),
         "w_gate": rnd(2, 3, 16, 12, scale=0.25),
         "w_up": rnd(2, 3, 16, 12, scale=0.25),
         "w_down": rnd(2, 3, 12, 16, scale=0.3)}
    tokens = np.array([3, 17, 5, 29, 8, 1, 30])
    got = np.asarray(MOE.logits(w, tokens, np.arange(7), d, bucket=8))
    # float32 against float64 loops over 2 layers
    np.testing.assert_allclose(got, _by_hand(w, tokens, d), rtol=1e-4,
                               atol=1e-4)
    # at the stated bfloat16 activations, each rounded where the loops
    # round it: float32 and float64 sums land on another bfloat16 value
    # now and then, one step (2^-8) of one element
    bf = dict(d, activations="bfloat16")
    got = np.asarray(MOE.logits(w, tokens, np.arange(7), bf, bucket=8))
    want = _by_hand(w, tokens, d, act=_bf16)
    scale = np.abs(want).max()
    np.testing.assert_allclose(got, want, atol=2e-3 * scale)
    # which a forward that rounds nothing misses
    assert np.abs(_by_hand(w, tokens, d) - want).max() > 2e-3 * scale


# -- a tiny cell through the harness ------------------------------------------

def _run(bench, capsys, extra=(), root=None):
    argv = ["--workload", CELL, "--seed", str(2**31 + 77), "--seconds", "2",
            "--trace", "0", *extra]
    kw = {"root": str(root)} if root else {}
    assert run.main(argv, here=str(bench), bench_root=str(bench),
                    require_chip=False, cache=False, **kw) == 0
    got = capsys.readouterr()
    return json.loads(got.out.strip().splitlines()[-1]), got.err


def test_tiny_cell_is_correct_and_both_controls_are_not(moe_bench, capsys):
    out, err = _run(moe_bench, capsys, extra=["--control", "bf16,fp8"])
    assert "program: correct True" in err
    assert "program: check payload_dtype = 'float32' (limit 'float32')" in err
    assert "control bf16: correct False" in err
    assert "control fp8: correct False" in err
    assert out["correct"] is False
    ck = out["checks"]
    assert ck["fp8.logit_gap"]["value"] > ck["fp8.logit_gap"]["limit"]
    assert ck["bf16.payload_dtype"] == {"value": "bfloat16",
                                        "limit": "float32"}
    assert set(out["metrics"]) == {"output_tok_per_s", "itl_p50_ms",
                                   "setup_s"}


def test_tiny_cell_traced_reports_its_counts(moe_bench, capsys, monkeypatch):
    # a CPU trace has no TPU plane and the CPU no peaks: both stood in for
    monkeypatch.setattr(tracing, "reduce", lambda path, chips=1: tracing.Trace(
        window_s=1.0, busy_s=0.5, steps=[], admits=[],
        breakdown={"device_ops": [], "idle_gaps": []}))
    monkeypatch.setitem(counts.PEAKS, "cpu", counts.PEAKS["TPU v5 lite"])
    out, err = _run(moe_bench, capsys, extra=["--trace", "1"])
    assert out["correct"] is True
    assert out["metrics"]["decode.mfu"]["value"] > 0
    assert "7 kernel roles x 4 layers" in err
