"""Plain forward of a routed-experts decoder stack with windowed and full
attention layers, in ``jax.numpy``, at one chip's share of experts, at the
precision the configuration states: float32 weights and arithmetic, and
activations held in ``activations`` (``precision.activations``).

The reference the served logits are judged by.  No kernels, no cache, no
batching: one sequence through every layer, each matmul in float32 under
``default_matmul_precision("highest")``.  It imports nothing of the program
under test and is given the benchmark's own weights (``arch/moe.py``).

Per layer l (pre-norm, residual), with window ``W_l`` (0: none) and RoPE
``θ_l`` (and YaRN parameters on full layers):

    h = rmsnorm(x) · (1 + ln1)
    q, k, v = h Wq, h Wk, h Wv        heads of ``head_dim``; n_kv_heads groups
    rotate q and k by position, pairs (2i, 2i+1), angle pos · f_i, cos and
        sin times the layer's attention factor, where f_i = θ^(-2i/D), or
        with YaRN (factor s, original length L0, β_fast, β_slow):
        f_i = f_i/s · (1 - γ_i) + f_i · γ_i,
        γ_i = 1 - clamp((i - lo) / (hi - lo), 0, 1),
        lo, hi = floor, ceil of D · ln(L0 / (β · 2π)) / (2 ln θ) at β_fast,
        β_slow
    a = softmax(q kᵀ / √head_dim) v over keys i - W_l < j ≤ i (all j ≤ i
        when W_l = 0), each q head reading its group
    x = x + a Wo
    h = rmsnorm(x) · (1 + ln2)
    p = softmax(h Wrouter) over all ``n_experts``; the ``top_k`` largest
        kept, renormalised to sum to 1 (``norm_topk_prob``)
    x = x + Σ_{e held, e in top_k} p_e · (silu(h Wgate_e) ⊙ h Wup_e) Wdown_e

then ``logits = (rmsnorm(x) · (1 + final_norm)) Headᵀ`` (an untied head).
The held experts are ``first_expert`` … ``first_expert + n_held - 1``: what
the other experts would add is left out, as the program leaves it out.
Interleaved pairs rotate the same plane as the published half-split layout
up to a fixed permutation of each head's features, which random weights do
not see.

Activations: every tensor a layer hands on is rounded to ``activations``
(bfloat16 in the configurations that state it): the embedded rows, each
norm's output, q, k and v before and after RoPE, the attention scores
(after the 1/√head_dim scale) and probabilities, the attention output,
each projection's and expert's output, the SwiGLU product, the gated sum
of the held experts and the residual stream after each add.  Sums, norms,
softmax and the router's scores are taken in float32 from those values; the
router therefore ranks the experts from the rows the layer holds, and a
token routes as the configuration's arithmetic routes it rather than as an
unrounded stream would.  ``activations`` float32 (or absent) rounds
nothing.

The controls, the reference again at a lower precision: ``quant="bf16"``
rounds every matmul operand to bfloat16 (the weights one step below their
stated float32), ``quant="fp8"`` to float8_e4m3fn with one scale per
tensor; each product is then taken in float32.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

LAYER_LEAVES = ("ln1", "ln2", "wq", "wk", "wv", "wo", "router", "w_gate",
                "w_up", "w_down")

#: The dtype each control serves its weights in.
CONTROL_DTYPES = {"bf16": "bfloat16", "fp8": "float8_e4m3fn"}


def _q(x, quant: str):
    if quant == "fp32":
        return x
    if quant == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if quant != "fp8":
        raise ValueError(f"unknown quant {quant!r}")
    f8 = jnp.float8_e4m3fn
    scale = jnp.max(jnp.abs(x)) / float(jnp.finfo(f8).max)
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(f8).astype(jnp.float32) * scale


def _mm(a, b, quant: str):
    return jnp.matmul(_q(a, quant), _q(b, quant))


def _act(dims: dict):
    """x → x rounded to the configuration's activation dtype (float32
    values back)."""
    dt = jnp.dtype(dims.get("activations", "float32"))
    if dt == jnp.float32:
        return lambda x: x
    return lambda x: x.astype(dt).astype(jnp.float32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w)


def inv_freq(rope: tuple, rot: int) -> np.ndarray:
    """(rot/2,) frequencies of ``rope`` = (θ, factor, original length,
    β_fast, β_slow, attention factor); default RoPE when factor ≤ 1."""
    theta, factor, orig, b_fast, b_slow, _ = rope
    f = theta ** (-np.arange(0, rot, 2, dtype=np.float64) / rot)
    if factor <= 1.0:
        return f

    def dim(beta):
        return rot * math.log(orig / (beta * 2 * math.pi)) \
            / (2 * math.log(theta))
    lo = max(math.floor(dim(b_fast)), 0)
    hi = min(math.ceil(dim(b_slow)), rot - 1)
    gamma = 1.0 - np.clip((np.arange(rot // 2) - lo) / (hi - lo), 0.0, 1.0)
    return f / factor * (1.0 - gamma) + f * gamma


def _rope(x, pos, freqs, scale):
    """x: (S, heads, head_dim), every feature pair rotated."""
    ang = pos[:, None, None].astype(jnp.float32) * freqs     # (S, 1, D/2)
    c, s = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * c - x2 * s, x1 * s + x2 * c], -1).reshape(x.shape)


def _attention(q, k, v, window, quant: str, act, q_chunk: int = 512):
    """Causal GQA attention over keys i - window < j <= i (all j <= i when
    ``window`` is 0), scores, probabilities and output rounded by ``act``.
    q: (S, H, D); k, v: (S, G, D)."""
    s, h, d = q.shape
    g = k.shape[1]
    qg = q.reshape(s, g, h // g, d)
    kq, vq = _q(k, quant), _q(v, quant)
    kpos = jnp.arange(s)
    chunk = math.gcd(q_chunk, s)

    def block(args):
        qc, qpos = args                                   # (C, G, R, D)
        sc = act(act(jnp.einsum("cgrd,sgd->grcs", _q(qc, quant), kq))
                 * act(jnp.float32(1.0 / math.sqrt(d))))
        see = (kpos[None, :] <= qpos[:, None]) & (
            (window == 0) | (kpos[None, :] > qpos[:, None] - window))
        sc = jnp.where(see[None, None], sc, -jnp.inf)
        p = act(jax.nn.softmax(sc, axis=-1))
        return act(jnp.einsum("grcs,sgd->cgrd", _q(p, quant), vq))

    n = s // chunk
    out = jax.lax.map(block, (qg.reshape(n, chunk, g, h // g, d),
                              jnp.arange(s).reshape(n, chunk)))
    return out.reshape(s, h * d)


def route(h, router, dims: dict, quant: str = "fp32"):
    """(gates (S, n_held), top-k experts (S, top_k)) of normed rows ``h``:
    each held expert's renormalised probability where it is among the
    ``top_k``, else 0."""
    p = jax.nn.softmax(_mm(h, router, quant), axis=-1)
    topw, tope = jax.lax.top_k(p, dims["top_k"])
    if dims["norm_topk_prob"]:
        topw = topw / jnp.sum(topw, -1, keepdims=True)
    held = dims["first_expert"] + jnp.arange(dims["n_held"])
    hit = tope[:, :, None] == held
    return jnp.sum(jnp.where(hit, topw[:, :, None], 0.0), axis=1), tope


def _layer(x, lw: dict, dims_t: tuple, layer: int, quant: str):
    """Layer ``layer``: its window and RoPE go to one compiled layer as
    arguments."""
    dims = dict(dims_t)
    rope = dims["rope"][layer]
    return _layer_at(x, lw, jnp.asarray(inv_freq(rope, dims["head_dim"]),
                                        jnp.float32),
                     jnp.float32(rope[5]), jnp.int32(dims["windows"][layer]),
                     dims_t, quant)


@functools.partial(jax.jit, static_argnums=(5, 6))
def _layer_at(x, lw: dict, freqs, scale, window, dims_t: tuple, quant: str):
    dims = dict(dims_t)
    act = _act(dims)
    s = x.shape[0]
    hd, nh, nk = dims["head_dim"], dims["n_heads"], dims["n_kv_heads"]
    pos = jnp.arange(s)
    h = act(_rms(x, lw["ln1"], dims["norm_eps"]))
    q = act(_rope(act(_mm(h, lw["wq"], quant)).reshape(s, nh, hd), pos,
                  freqs, scale))
    k = act(_rope(act(_mm(h, lw["wk"], quant)).reshape(s, nk, hd), pos,
                  freqs, scale))
    v = act(_mm(h, lw["wv"], quant)).reshape(s, nk, hd)
    a = _attention(q, k, v, window, quant, act)
    x = act(x + act(_mm(a, lw["wo"], quant)))
    h = act(_rms(x, lw["ln2"], dims["norm_eps"]))
    gates, _ = route(h, lw["router"], dims, quant)
    y = 0.0
    for e in range(dims["n_held"]):
        f = act(act(jax.nn.silu(act(_mm(h, lw["w_gate"][e], quant))))
                * act(_mm(h, lw["w_up"][e], quant)))
        y = y + gates[:, e:e + 1] * act(_mm(f, lw["w_down"][e], quant))
    return act(x + act(y))


@functools.partial(jax.jit, static_argnums=(4, 5))
def _head(x, rows, final_norm, head, dims_t: tuple, quant: str):
    dims = dict(dims_t)
    h = _act(dims)(_rms(x[rows], final_norm, dims["norm_eps"]))
    return _mm(h, head.T, quant)


def logits(w: dict, tokens, rows, dims: dict, quant: str = "fp32",
           bucket: int = 512):
    """Logits (len(rows), vocab) of ``tokens`` at positions ``rows``.

    The sequence is padded at its end to a multiple of ``bucket`` (causal
    attention keeps the padding out of every earlier position), so that
    lengths share compiled layers."""
    tokens = jnp.asarray(tokens, jnp.int32)
    pad = -tokens.shape[0] % bucket
    tokens = jnp.pad(tokens, (0, pad))
    dims_t = tuple(sorted(dims.items()))
    with jax.default_matmul_precision("highest"):
        x = _act(dims)(jnp.take(w["embed"], tokens, axis=0))
        for layer in range(dims["n_layers"]):
            x = _layer(x, {k: w[k][layer] for k in LAYER_LEAVES}, dims_t,
                       layer, quant)
        return _head(x, jnp.asarray(rows, jnp.int32), w["final_norm"],
                     w["head"], dims_t, quant)
