"""Attention: chunked causal (flash-style online softmax via lax.scan),
sliding-window local attention, and single-token decode against a KV cache.

Memory discipline: full (S, S) score matrices are never materialized — the
KV axis is scanned in chunks with a running (max, denominator, numerator)
accumulator, so peak live memory is O(B · H · Sq_chunk · Skv_chunk).  This is
what keeps prefill_32k compilable; on TPU the same schedule is what a Pallas
flash kernel would pin into VMEM.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import optflags
from repro.models.layers import COMPUTE_DTYPE, apply_rope
from repro.models.sharding import shard

NEG_INF = -1e30


def _repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    """(B, S, Hkv, D) → (B, S, Hkv·n_rep, D) for GQA."""
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d)) \
        .reshape(b, s, h * n_rep, d)


def chunked_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      causal: bool = True, window: int = 0,
                      q_chunk: int = 512, kv_chunk: int = 512,
                      q_offset: int = 0) -> jax.Array:
    """Online-softmax attention.

    q: (B, Sq, H, D); k/v: (B, Skv, H, D) (same H after GQA repeat).
    ``window`` > 0 restricts attention to the last ``window`` keys (local);
    a traced ``window`` (the layer scan's per-layer value) does so where it
    is > 0.
    ``q_offset``: absolute position of q[0] relative to k[0] (prefill = 0
    with Sq == Skv; decode uses decode_attention instead).
    """
    b, sq, h, d = q.shape
    skv = k.shape[1]
    scale = 1.0 / math.sqrt(d)
    q_chunk = min(q_chunk, sq)
    kv_chunk = min(kv_chunk, skv)
    nq = (sq + q_chunk - 1) // q_chunk
    nk = (skv + kv_chunk - 1) // kv_chunk
    # pad to whole chunks
    sq_p, skv_p = nq * q_chunk, nk * kv_chunk
    qp = jnp.pad(q, ((0, 0), (0, sq_p - sq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, skv_p - skv), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, skv_p - skv), (0, 0), (0, 0)))

    qs = jnp.moveaxis(qp.reshape(b, nq, q_chunk, h, d), 1, 0)
    ks = jnp.moveaxis(kp.reshape(b, nk, kv_chunk, h, d), 1, 0)
    vs = jnp.moveaxis(vp.reshape(b, nk, kv_chunk, h, d), 1, 0)
    q_pos = q_offset + jnp.arange(sq_p).reshape(nq, q_chunk)
    k_pos = jnp.arange(skv_p).reshape(nk, kv_chunk)
    k_valid = (jnp.arange(skv_p) < skv).reshape(nk, kv_chunk)

    def q_block(args):
        qi, qpos = args                     # (B, qc, H, D), (qc,)

        def kv_step(carry, inp):
            m, l, acc = carry
            ki, vi, kpos, kval = inp
            s = jnp.einsum("bqhd,bkhd->bhqk", qi, ki) * scale
            mask = kval[None, None, None, :]
            if causal:
                mask = mask & (kpos[None, None, None, :] <=
                               qpos[None, None, :, None])
            if isinstance(window, jax.Array):
                mask = mask & ((window <= 0) | (kpos[None, None, None, :] >
                                                qpos[None, None, :, None]
                                                - window))
            elif window > 0:
                mask = mask & (kpos[None, None, None, :] >
                               qpos[None, None, :, None] - window)
            s = jnp.where(mask, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p.astype(COMPUTE_DTYPE), vi)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, h, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, h, q_chunk), jnp.float32)
        a0 = jnp.zeros((b, h, q_chunk, d), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0),
                                      (ks, vs, k_pos, k_valid))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return jnp.moveaxis(out, 1, 2)      # (B, qc, H, D)

    outs = jax.lax.map(q_block, (qs, q_pos))            # (nq, B, qc, H, D)
    out = jnp.moveaxis(outs, 0, 1).reshape(b, sq_p, h, d)[:, :sq]
    return out.astype(q.dtype)


def _valid_mask(s: int, length: jax.Array) -> jax.Array:
    """(1 | B, S) validity mask from a scalar or per-row (B,) ``length``.

    Per-row lengths are the mixer's per-slot causal mask: every batch row
    (= KV slot) attends to its OWN prefix only, so slots at different
    positions — or stale KV left by an evicted request — never leak."""
    return jnp.arange(s)[None, :] < jnp.reshape(length, (-1, 1))


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     length: jax.Array,
                     window: Optional[jax.Array] = None) -> jax.Array:
    """One-token attention against a cache, one pass over each KV group.

    q: (B, H, D); caches: (B, S, Hkv, D) with H = r·Hkv (r = 1 is MHA);
    ``length``: number of valid cache positions — a scalar, or (B,)
    per-slot lengths for mixed-position batches.  ``window`` (traced,
    > 0) also hides positions ``< length - window``: the query at
    position i sees keys ``i - window < j <= i``.  The query is grouped
    to (B, Hkv, r, D) and contracted against the cache in its stored
    layout, so no (B, S, H, D) copy of the cache is built; with S
    model-sharded only the (B, Hkv, r)-sized softmax statistics and
    output partials cross shards, never the cache.  Cost is linear in
    S — this is the decode_32k / long_500k step.
    """
    b, s, hk, d = k_cache.shape
    h = q.shape[1]
    if h % hk:
        raise ValueError(f"{h} query heads do not group over {hk} KV heads")
    r = h // hk
    qg = q.reshape(b, hk, r, d)
    scale = 1.0 / math.sqrt(d)
    scores = jnp.einsum("bgrd,bsgd->bgrs", qg, k_cache) * scale
    valid = _valid_mask(s, length)
    if window is not None:
        lo = jnp.where(window > 0, jnp.reshape(length, (-1, 1)) - window, 0)
        valid = valid & (jnp.arange(s)[None, :] >= lo)
    valid = valid[:, None, None, :]                     # (1 | B, 1, 1, S)
    scores = jnp.where(valid, scores, NEG_INF)
    w = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    out = jnp.einsum("bgrs,bsgd->bgrd", w.astype(COMPUTE_DTYPE), v_cache)
    return out.reshape(b, h, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# Full attention block (projections + rope + attention + out-proj)
# ---------------------------------------------------------------------------

def attention_block(x: jax.Array, p: dict, cfg: ModelConfig,
                    freqs: Optional[jax.Array], positions: jax.Array,
                    causal: bool = True, window: int = 0,
                    kv_override: Optional[tuple[jax.Array, jax.Array]] = None,
                    return_kv: bool = False,
                    rope_scale: Optional[jax.Array] = None):
    """Training/prefill attention over a full sequence.

    ``kv_override`` supplies external K/V inputs (cross-attention).
    ``return_kv=True`` additionally returns the pre-GQA-repeat (K, V) —
    post-RoPE K, exactly what :func:`attention_decode_block` writes into
    the decode cache — so prefill can fill the cache in one batched pass."""
    b, s, _ = x.shape
    nh, nk, hd = L.eff_heads(cfg.n_heads), cfg.n_kv_heads, cfg.head_dim
    q = L.proj(x, p["wq"], "attn.wq")
    q = q.reshape(b, s, nh, hd)
    if kv_override is None:
        k = L.proj(x, p["wk"], "attn.wk")
        v = L.proj(x, p["wv"], "attn.wv")
        k = k.reshape(b, s, nk, hd)
        v = v.reshape(b, s, nk, hd)
        k = apply_rope(k, positions, freqs, rope_scale)
    else:
        assert not return_kv, "return_kv only applies to self-attention"
        xkv = kv_override[0]
        skv = xkv.shape[1]
        k = L.proj(xkv, p["wk"], "attn.wk")
        v = L.proj(xkv, p["wv"], "attn.wv")
        k = k.reshape(b, skv, nk, hd)
        v = v.reshape(b, skv, nk, hd)
    q = apply_rope(q, positions, freqs, rope_scale)
    q = shard(q, "batch", None, "model", None)
    k = shard(k, "batch", None, "model", None)
    rep = nh // max(nk, 1)
    with jax.named_scope("attention"):
        kr, vr = _repeat_kv(k, rep), _repeat_kv(v, rep)
        o = chunked_attention(q, kr, vr, causal=causal, window=window)
    o = o.reshape(b, s, nh * hd)
    out = L.proj(o, p["wo"], "attn.wo")
    if return_kv:
        return out, k, v
    return out


def attention_decode_block(x: jax.Array, p: dict, cfg: ModelConfig,
                           freqs: Optional[jax.Array], pos: jax.Array,
                           k_cache: jax.Array, v_cache: jax.Array,
                           cache_pos: jax.Array,
                           window: Optional[jax.Array] = None,
                           rope_scale: Optional[jax.Array] = None,
                           ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Single-token attention step.

    x: (B, d).  Caches (B, S, Hkv, D) are updated at ``cache_pos`` (ring
    position for sliding windows; == pos for full caches).  ``pos`` /
    ``cache_pos`` are scalars (lockstep batch) or (B,) per-slot vectors
    (the mixer's mixed-position batch: every row rotates, writes, and
    masks at its OWN position).  ``window`` / ``rope_scale``: the layer's
    traced window and RoPE scale (:func:`repro.models.layers.layer_attn_xs`).
    Returns (out (B, d), new_k_cache, new_v_cache).
    """
    b, _ = x.shape
    nh, nk, hd = L.eff_heads(cfg.n_heads), cfg.n_kv_heads, cfg.head_dim
    pos = jnp.asarray(pos)
    cache_pos = jnp.asarray(cache_pos)
    q = L.proj(x, p["wq"], "attn.wq")
    k = L.proj(x, p["wk"], "attn.wk")
    v = L.proj(x, p["wv"], "attn.wv")
    pos1 = jnp.reshape(pos, (b, 1)) if pos.ndim else jnp.reshape(pos, (1,))
    q = apply_rope(q.reshape(b, 1, nh, hd), pos1, freqs,
                   rope_scale).reshape(b, nh, hd)
    k = apply_rope(k.reshape(b, 1, nk, hd), pos1, freqs,
                   rope_scale).reshape(b, nk, hd)
    v = v.reshape(b, nk, hd)
    with jax.named_scope("kv_write"):
        if optflags.enabled("maskedkv") or cache_pos.ndim:
            # one-hot masked blend: elementwise along the (possibly model-
            # sharded) S axis — no replicate-and-repartition, unlike a
            # dynamic update at a traced index.  Costs one cache-sized RMW
            # pass.  A per-slot (B,) cache_pos always takes this path (each
            # row writes at its own position — dynamic_update_slice cannot).
            hot = (jnp.arange(k_cache.shape[1])[None, :] ==
                   jnp.reshape(cache_pos, (-1, 1)))[:, :, None, None]
            k_cache = jnp.where(hot, k[:, None].astype(k_cache.dtype),
                                k_cache)
            v_cache = jnp.where(hot, v[:, None].astype(v_cache.dtype),
                                v_cache)
        else:
            k_cache = jax.lax.dynamic_update_slice_in_dim(
                k_cache, k[:, None].astype(k_cache.dtype), cache_pos, axis=1)
            v_cache = jax.lax.dynamic_update_slice_in_dim(
                v_cache, v[:, None].astype(v_cache.dtype), cache_pos, axis=1)
    s_max = k_cache.shape[1]
    length = jnp.minimum(pos + 1, s_max)
    with jax.named_scope("attention"):
        o = decode_attention(q, k_cache, v_cache, length, window)
    o = o.reshape(b, nh * hd)
    out = L.proj(o, p["wo"], "attn.wo")
    return out, k_cache, v_cache
