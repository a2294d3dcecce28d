"""Flash attention — Pallas TPU kernel (online softmax, causal).

The chunked-attention schedule of ``models/attention.py`` (lax.scan online
softmax) pinned into VMEM: one (bq, d) query tile stays resident while the
KV axis streams through in (bk, d) tiles; the running (max, denom,
accumulator) lives in VMEM scratch.  Causal blocks strictly above the
diagonal are skipped with ``pl.when`` (no FLOPs, no traffic).

Grid: (B·H, Sq/bq, Skv/bk), KV innermost ("arbitrary" — sequential per
output tile); q/o tiles are revisited across the KV axis.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
            *, scale: float, causal: bool, bq: int, bk: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # causal: the whole KV tile is masked when its first row starts after
    # the query tile's last position → skip compute AND traffic
    live = (not causal) or (ki * bk <= qi * bq + bq - 1)

    @pl.when(live)
    def _step():
        q = q_ref[0]                            # (bq, d)
        k = k_ref[0]                            # (bk, d)
        v = v_ref[0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32,
                                                      (bq, bk), 0)
            kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32,
                                                      (bq, bk), 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                           *, causal: bool = True, bq: int = 128,
                           bk: int = 128, interpret: bool = False
                           ) -> jax.Array:
    """q/k/v: (BH, S, D) — batch and heads pre-flattened (GQA repeat done by
    the caller or avoided via grouped layouts).  Returns (BH, Sq, D)."""
    bh, sq, d = q.shape
    skv = k.shape[1]
    bq = min(bq, sq)
    bk = min(bk, skv)
    assert sq % bq == 0 and skv % bk == 0, (sq, skv, bq, bk)
    grid = (bh, sq // bq, skv // bk)
    scale = 1.0 / math.sqrt(d)

    kernel = functools.partial(_kernel, scale=scale, causal=causal,
                               bq=bq, bk=bk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, bk, d), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, bk, d), lambda b, qi, ki: (b, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(q, k, v)
