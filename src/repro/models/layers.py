"""Core layers: norms, embeddings, RoPE, MLP, parameter init.

Pure-functional JAX: params are nested dicts of arrays; every layer is a
plain function.  Layer stacks are STACKED along a leading axis and consumed
by ``jax.lax.scan`` (transformer.py) so that HLO size stays O(1) in depth —
essential for compiling 62-layer models on 512 host devices.
"""

from __future__ import annotations

import contextlib
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, RopeCfg
from repro.models import optflags
from repro.models.sharding import shard

TP_DEGREE = 16   # production model-axis size (padheads rounds up to this)


def eff_heads(n: int) -> int:
    """Head count after optional pad-to-TP-multiple (optflags 'padheads')."""
    if optflags.enabled("padheads") and n % TP_DEGREE:
        return ((n // TP_DEGREE) + 1) * TP_DEGREE
    return n


Dtype = jnp.dtype
COMPUTE_DTYPE = jnp.bfloat16
PARAM_DTYPE = jnp.float32


# ---------------------------------------------------------------------------
# Projection dispatch hook (execution plane)
# ---------------------------------------------------------------------------
# Every FFN/attention projection matmul routes through :func:`proj`.  With no
# hook installed this is exactly the dense einsum the layers always ran; the
# exec plane (repro.exec.dispatch) installs a hook that swaps individual
# (layer, role) projections for compressed Pallas kernels per its ExecPlan.

_PROJ_HOOK = None


def set_proj_hook(fn) -> None:
    """Install (or clear, with ``None``) the projection override.

    ``fn(x, w, role) -> Optional[jax.Array]``: return the projection output
    (same leading dims as ``x``, trailing dim from ``w``) to take over the
    matmul, or ``None`` to fall through to the dense einsum."""
    global _PROJ_HOOK
    _PROJ_HOOK = fn


def proj_hooked() -> bool:
    """Whether a projection hook is installed (the execution plane serves
    some projections)."""
    return _PROJ_HOOK is not None


def proj(x: jax.Array, w: jax.Array, role: str) -> jax.Array:
    """``x @ w`` over the last axis of ``x`` (the layers' projection shape:
    w is (d_in, d_out)), dispatchable per ``role``; its device ops carry
    ``role`` as their name scope, dense or kernel-served.

    Expert weights ``w`` (E, d_in, d_out) give (E, T, d_out): every
    expert's product with ``x`` (T, d_in), or with its own row of ``x``
    (E, T, d_in)."""
    with jax.named_scope(role):
        if _PROJ_HOOK is not None:
            y = _PROJ_HOOK(x, w, role)
            if y is not None:
                return y
        if w.ndim == 3:
            spec = "td,edf->etf" if x.ndim == 2 else "etd,edf->etf"
            return jnp.einsum(spec, x, w.astype(COMPUTE_DTYPE))
        return jnp.einsum("...d,df->...f", x, w.astype(COMPUTE_DTYPE))


# The hook's per-layer operand channel.  The transformer's scan runners
# thread an optional ``extras`` pytree (leading layer axis) through
# ``lax.scan`` and install each layer's SLICE here around the layer body,
# so a hook can resolve layer-varying operands (compressed weights) while
# the compiled graph stays one scanned block.  Trace-time state only.

_LAYER_CTX: Any = None


@contextlib.contextmanager
def layer_ctx(value: Any):
    """Install the current layer's extras slice for the proj hook."""
    global _LAYER_CTX
    prev = _LAYER_CTX
    _LAYER_CTX = value
    try:
        yield
    finally:
        _LAYER_CTX = prev


def current_layer_ctx() -> Any:
    """The per-layer extras slice the enclosing scan body installed."""
    return _LAYER_CTX


def _init(key, shape, scale_axis: int = 0, dtype=PARAM_DTYPE):
    fan_in = shape[scale_axis]
    return jax.random.normal(key, shape, dtype) / math.sqrt(max(fan_in, 1))


def rms_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * (1.0 + weight.astype(jnp.float32))).astype(dt)


def embed(tokens: jax.Array, table: jax.Array) -> jax.Array:
    """Token embedding with vocab-sharded table (gather lowers to a sharded
    take; XLA inserts the all-gather on the vocab axis)."""
    out = jnp.take(table, tokens, axis=0).astype(COMPUTE_DTYPE)
    return shard(out, "batch", None, None)


def unembed_loss(x: jax.Array, table: jax.Array, labels: jax.Array,
                 chunk: int = 512) -> jax.Array:
    """Next-token cross-entropy with sequence-chunked logits.

    Never materializes (B, S, V); scans over S in ``chunk`` slices so the
    live logits buffer is (B, chunk, V) — sharded over batch(data) and
    vocab(model).  Returns mean loss over all positions.
    """
    b, s, d = x.shape
    v = table.shape[0]
    n_chunks = max(s // chunk, 1)
    chunk = s // n_chunks
    xc = x[:, : n_chunks * chunk].reshape(b, n_chunks, chunk, d)
    yc = labels[:, : n_chunks * chunk].reshape(b, n_chunks, chunk)
    xc = jnp.moveaxis(xc, 1, 0)          # (n_chunks, B, chunk, d)
    yc = jnp.moveaxis(yc, 1, 0)

    tbl = table.astype(COMPUTE_DTYPE)

    def body(carry, inp):
        xi, yi = inp
        logits = jnp.einsum("btd,vd->btv", xi, tbl).astype(jnp.float32)
        logits = shard(logits, "batch", None, "vocab")
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, yi[..., None], axis=-1)[..., 0]
        return carry + jnp.sum(lse - gold), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (xc, yc))
    return total / (b * n_chunks * chunk)


def head_table(params: dict) -> jax.Array:
    """The output head's (vocab, d) table: ``params["head"]`` when the
    model has its own, else the embedding (tied)."""
    return params["head"] if "head" in params else params["embed"]


@jax.named_scope("head")
def logits_head(x: jax.Array, table: jax.Array) -> jax.Array:
    """Decode-time logits for the last position only: (B, V)."""
    logits = jnp.einsum("bd,vd->bv", x, table.astype(COMPUTE_DTYPE))
    return shard(logits.astype(jnp.float32), "batch", "vocab")


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def _rot(cfg: ModelConfig) -> int:
    rot = int(cfg.head_dim * cfg.rope_fraction)
    return rot - rot % 2


def rope_freqs(cfg: ModelConfig) -> Optional[jax.Array]:
    if cfg.rope_fraction <= 0.0:
        return None
    rot = _rot(cfg)
    return cfg.rope_base ** (-jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)


def rope_inv_freq(rope: RopeCfg, rot: int) -> np.ndarray:
    """Inverse frequencies (rot/2,) of one layer kind, float64.

    Default: ``theta^(-2i/rot)``.  YaRN (``factor`` > 1): with ``extrap``
    those and ``interp = extrap / factor``, ``inv_freq = interp·(1-γ) +
    extrap·γ``, ``γ = 1 - clamp((i - lo)/(hi - lo), 0, 1)``, where ``lo``
    and ``hi`` are the floor and ceil of the dimension that turns
    ``beta_fast`` and ``beta_slow`` times over ``original_max`` positions,
    ``rot·ln(original_max / (β·2π)) / (2 ln θ)``."""
    extrap = rope.theta ** (-np.arange(0, rot, 2, dtype=np.float64) / rot)
    if rope.factor <= 1.0:
        return extrap

    def dim(beta):
        return rot * math.log(rope.original_max / (beta * 2 * math.pi)) \
            / (2 * math.log(rope.theta))
    lo = max(math.floor(dim(rope.beta_fast)), 0)
    hi = min(math.ceil(dim(rope.beta_slow)), rot - 1)
    hi = hi + 0.001 if hi == lo else hi
    ramp = np.clip((np.arange(rot // 2) - lo) / (hi - lo), 0.0, 1.0)
    gamma = 1.0 - ramp
    return extrap / rope.factor * (1.0 - gamma) + extrap * gamma


def layer_attn_xs(cfg: ModelConfig) -> Optional[dict]:
    """Per-layer attention settings that ride the layer scan as ``xs``:
    ``window`` (L,) int32 (0: full causal), RoPE ``freqs`` (L, rot/2) and
    ``rope_scale`` (L,) float32.  None for a stack without
    ``attn_pattern``, whose layers share ``cfg.window`` and
    :func:`rope_freqs`."""
    kinds = cfg.layer_attn
    if not kinds:
        return None
    rot = _rot(cfg)
    return {"window": jnp.asarray([k.window for k in kinds], jnp.int32),
            "freqs": jnp.asarray(np.stack(
                [rope_inv_freq(k.rope, rot) for k in kinds]), jnp.float32),
            "rope_scale": jnp.asarray(
                [k.rope.attention_factor for k in kinds], jnp.float32)}


def apply_rope(x: jax.Array, positions: jax.Array, freqs: Optional[jax.Array],
               scale: Optional[jax.Array] = None) -> jax.Array:
    """x: (..., S, H, D); positions: broadcastable to (..., S).

    Applies rotary embedding to the first ``2·len(freqs)`` features of D
    (``rope_fraction`` < 1 leaves the tail untouched — ChatGLM-style);
    ``scale`` multiplies cos and sin (YaRN's attention factor)."""
    if freqs is None:
        return x
    rot = 2 * freqs.shape[0]
    ang = positions[..., None].astype(jnp.float32) * freqs       # (..., S, rot/2)
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    if scale is not None:
        cos, sin = cos * scale, sin * scale
    xr = x[..., :rot].astype(jnp.float32)
    x1, x2 = xr[..., 0::2], xr[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    out = out.reshape(xr.shape).astype(x.dtype)
    return jnp.concatenate([out, x[..., rot:]], axis=-1) if rot < x.shape[-1] else out


# ---------------------------------------------------------------------------
# MLP (SwiGLU) + params
# ---------------------------------------------------------------------------

def mlp_params(key, cfg: ModelConfig) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    d, f = cfg.d_model, cfg.d_ff
    return {
        "w_gate": _init(k1, (d, f)),
        "w_up": _init(k2, (d, f)),
        "w_down": _init(k3, (f, d)),
    }


def mlp(x: jax.Array, p: dict) -> jax.Array:
    g = proj(x, p["w_gate"], "ffn.w_gate")
    u = proj(x, p["w_up"], "ffn.w_up")
    h = jax.nn.silu(g) * u
    h = shard(h, "batch", None, "model")
    return proj(h, p["w_down"], "ffn.w_down")


def attn_params(key, cfg: ModelConfig) -> dict:
    kq, kk, kv, ko = jax.random.split(key, 4)
    d, h, nk = cfg.d_model, cfg.head_dim, cfg.n_kv_heads
    nh = eff_heads(cfg.n_heads)
    return {
        "wq": _init(kq, (d, nh * h)),
        "wk": _init(kk, (d, nk * h)),
        "wv": _init(kv, (d, nk * h)),
        "wo": _init(ko, (nh * h, d)),
    }


# ---------------------------------------------------------------------------
# Block-sparse FFN (serve path, optflags 'sparseffn')
# ---------------------------------------------------------------------------

def sparse_mlp_params(key, cfg: ModelConfig, density: float = 0.25,
                      bn: int = 128, bk: int = 128) -> dict:
    """FFN up/gate weights in the SnipSnap-chosen block-bitmap format:
    per-block-column padded payload (gk, T, bn, bk) + block-row ids.
    w_down stays dense (its contraction dim is model-sharded; gathering
    across shards would trade memory for collectives)."""
    k1, k2, k3, k4 = jax.random.split(key, 4)
    d, f = cfg.d_model, cfg.d_ff
    gn, gk = d // bn, f // bk
    t = max(1, int(gn * density))
    return {
        "payload_gate": _init(k1, (gk, t, bn, bk), scale_axis=2),
        "rows_gate": jnp.zeros((gk, t), jnp.int32),
        "payload_up": _init(k2, (gk, t, bn, bk), scale_axis=2),
        "rows_up": jnp.zeros((gk, t), jnp.int32),
        "w_down": _init(k3, (f, d)),
        "_meta": jnp.array([bn, bk], jnp.int32),
    }


def _bsp_matmul(x: jax.Array, payload: jax.Array, rows: jax.Array
                ) -> jax.Array:
    """x: (B, N); payload: (gk, T, bn, bk); rows: (gk, T) block-row ids.
    Streams ONLY the non-zero payload blocks (the compressed format's win:
    weight traffic × block density)."""
    b, n = x.shape
    gk, t, bn, bk = payload.shape
    xb = x.reshape(b, n // bn, bn)
    xsel = jnp.take(xb, rows.reshape(-1), axis=1)       # (B, gk·T, bn)
    xsel = xsel.reshape(b, gk, t, bn)
    y = jnp.einsum("bgtn,gtnk->bgk", xsel,
                   payload.astype(COMPUTE_DTYPE))
    return y.reshape(b, gk * bk)


def sparse_mlp_decode(x: jax.Array, p: dict) -> jax.Array:
    """Single-token SwiGLU FFN over block-compressed up/gate weights."""
    g = _bsp_matmul(x, p["payload_gate"], p["rows_gate"])
    u = _bsp_matmul(x, p["payload_up"], p["rows_up"])
    h = jax.nn.silu(g) * u
    h = shard(h, "batch", "model")
    return jnp.einsum("bf,fd->bd", h, p["w_down"].astype(COMPUTE_DTYPE))
