"""Plain float32 forward of a dense GQA decoder stack, in ``jax.numpy``.

The reference the served logits are judged by.  No kernels, no cache, no
batching: one sequence through every layer, each matmul in float32 under
``default_matmul_precision("highest")``.  It imports nothing of the program
under test and is given the benchmark's own weights (``weights.py``).

Per layer (pre-norm, residual):

    h = rmsnorm(x) · (1 + ln1)
    q, k, v = h Wq, h Wk, h Wv        heads of ``head_dim``; n_kv_heads groups
    rotate the first ``rope_fraction`` of each q and k head by position:
        pairs (2i, 2i+1), angle pos · rope_base^(-2i / rot)
    a = softmax(q kᵀ / √head_dim, causal) v, each q head reading its group
    x = x + a Wo
    h = rmsnorm(x) · (1 + ln2)
    x = x + (silu(h Wgate) ⊙ h Wup) Wdown

then ``logits = (rmsnorm(x) · (1 + final_norm)) Embedᵀ`` (the head tied to
the embedding).  ChatGLM3 rotates half of each head (``rope_fraction``
0.5); DeepSeek-Coder rotates all of it.  Interleaved pairs rotate the same
plane as the published half-split layout up to a fixed permutation of each
head's features, which random weights do not see.

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

LAYER_LEAVES = ("ln1", "ln2", "wq", "wk", "wv", "wo", "w_gate", "w_up",
                "w_down")


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


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w)


def _rope(x, pos, dims):
    """x: (S, heads, head_dim)."""
    rot = int(dims["head_dim"] * dims["rope_fraction"])
    rot -= rot % 2
    if rot == 0:
        return x
    inv = dims["rope_base"] ** (-jnp.arange(0, rot, 2, dtype=jnp.float32)
                                / rot)
    ang = pos[:, None, None].astype(jnp.float32) * inv      # (S, 1, rot/2)
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0:rot:2], x[..., 1:rot:2]
    r = jnp.stack([x1 * c - x2 * s, x1 * s + x2 * c], -1).reshape(
        x.shape[:-1] + (rot,))
    return jnp.concatenate([r, x[..., rot:]], -1)


def _attention(q, k, v, quant: str, q_chunk: int = 512):
    """Causal GQA attention. q: (S, H, D); k, v: (S, G, D)."""
    s, h, d = q.shape
    g = k.shape[1]
    qg = q.reshape(s, g, h // g, d)
    kq, vq = _q(k, quant), _q(v, quant)
    kpos = jnp.arange(s)
    chunk = math.gcd(q_chunk, s)

    def block(args):
        qc, qpos = args                                   # (C, G, R, D)
        sc = jnp.einsum("cgrd,sgd->grcs", _q(qc, quant), kq) / math.sqrt(d)
        sc = jnp.where(kpos[None, None, None, :] <= qpos[None, None, :, None],
                       sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("grcs,sgd->cgrd", _q(p, quant), vq)

    n = s // chunk
    out = jax.lax.map(block, (qg.reshape(n, chunk, g, h // g, d),
                              jnp.arange(s).reshape(n, chunk)))
    return out.reshape(s, h * d)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _layer(x, lw: dict, dims_t: tuple, quant: str):
    dims = dict(dims_t)
    s = x.shape[0]
    hd, nh, nk = dims["head_dim"], dims["n_heads"], dims["n_kv_heads"]
    pos = jnp.arange(s)
    h = _rms(x, lw["ln1"], dims["norm_eps"])
    q = _rope(_mm(h, lw["wq"], quant).reshape(s, nh, hd), pos, dims)
    k = _rope(_mm(h, lw["wk"], quant).reshape(s, nk, hd), pos, dims)
    v = _mm(h, lw["wv"], quant).reshape(s, nk, hd)
    x = x + _mm(_attention(q, k, v, quant), lw["wo"], quant)
    h = _rms(x, lw["ln2"], dims["norm_eps"])
    f = jax.nn.silu(_mm(h, lw["w_gate"], quant)) * _mm(h, lw["w_up"], quant)
    return x + _mm(f, lw["w_down"], quant)


@functools.partial(jax.jit, static_argnums=(4, 5))
def _head(x, rows, final_norm, embed, eps: float, quant: str):
    h = _rms(x[rows], final_norm, eps)
    return _mm(h, embed.T, quant)


def logits(w: dict, tokens, rows, dims: dict, quant: str = "fp32",
           bucket: int = 512):
    """Logits (len(rows), vocab) of ``tokens`` at positions ``rows``.

    The sequence is padded at its end to a multiple of ``bucket`` (causal
    attention keeps the padding out of every earlier position), so that
    lengths share compiled layers."""
    tokens = jnp.asarray(tokens, jnp.int32)
    s = tokens.shape[0]
    pad = -s % bucket
    tokens = jnp.pad(tokens, (0, pad))
    dims_t = tuple(sorted(dims.items()))
    with jax.default_matmul_precision("highest"):
        x = jnp.take(w["embed"], tokens, axis=0)
        for layer in range(dims["n_layers"]):
            x = _layer(x, {k: w[k][layer] for k in LAYER_LEAVES}, dims_t,
                       quant)
        return _head(x, jnp.asarray(rows, jnp.int32), w["final_norm"],
                     w["embed"], dims["norm_eps"], quant)
