"""Mixture of experts at one chip's share: route over every expert, add the
part of the experts held here.

The router scores all ``n_experts`` in float32: ``softmax(h W_router)``,
the ``top_k`` best kept and, with ``norm_topk_prob``, renormalised to sum
to 1.  This layer holds experts ``[first_expert, first_expert + held)``
(:class:`~repro.configs.base.MoECfg`); held expert e adds
``g_e · W_down,e (silu(W_gate,e h) ⊙ W_up,e h)``, with ``g_e`` its gate for
a row routed to it and 0 otherwise.  What the absent experts would add is
left out: under expert parallelism the other chips add it.

No row is dropped, in prefill or decode.  Two ways, chosen by the rows of
the call and by who serves the projections:

* every row through every held expert (the gate zeroes the rows not routed
  to it), when the rows fit one row tile (``tiling.ROW_TILE_CAP``, so every
  decode step) or when a projection hook is installed (the execution
  plane's compressed kernels take a static row count per held expert).
  Each held expert's weights are then streamed once per row tile, as they
  must be once some row of the step routes to it; the FLOPs are
  ``held / (top_k · held / n_experts)`` times the routed ones (8× at 16
  held of 64, top 8; what compressed prefill pays);
* rows grouped by held expert (``jax.lax.ragged_dot``) when the rows exceed
  a tile and the weights are dense (training, dense prefill): each
  (row, expert) pair the router chose is computed once, so the FLOPs are
  the routed ones and the buffers hold ``rows · min(top_k, held)`` rows.

The expert projections carry their role's name scope either way; on the
first path they go through :func:`repro.models.layers.proj` with (E, n, k)
weights, so the execution plane serves each held expert by its own
compressed kernel.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels.tiling import ROW_TILE_CAP
from repro.models import layers as L
from repro.models.layers import COMPUTE_DTYPE, _init


def moe_params(key, cfg: ModelConfig) -> dict:
    """The router over all experts and the held experts' weights."""
    m = cfg.moe
    assert m is not None
    kr, k1, k2, k3 = jax.random.split(key, 4)
    d, f, e = cfg.d_model, m.d_expert, m.held
    return {
        "router": _init(kr, (d, m.n_experts)),
        "w_gate": _init(k1, (e, d, f), scale_axis=1),
        "w_up": _init(k2, (e, d, f), scale_axis=1),
        "w_down": _init(k3, (e, f, d), scale_axis=1),
    }


def _scores(x: jax.Array, router: jax.Array, cfg: ModelConfig
            ) -> tuple[jax.Array, jax.Array]:
    """x (T, d) → (top-k weights (T, k) float32, top-k experts (T, k))."""
    m = cfg.moe
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                        router.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    topw, tope = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), m.top_k)
    if m.norm_topk_prob:
        topw = topw / jnp.sum(topw, -1, keepdims=True)
    return topw, tope


def _held(topw: jax.Array, tope: jax.Array, cfg: ModelConfig
          ) -> tuple[jax.Array, jax.Array]:
    """(gates (T, held) float32, routed (T, held) bool): each held expert's
    gate for each row (0 where the row is not routed to it)."""
    m = cfg.moe
    held = m.first_expert + jnp.arange(m.held)
    hit = tope[:, :, None] == held                          # (T, k, held)
    gates = jnp.sum(jnp.where(hit, topw[:, :, None], 0.0), axis=1)
    return gates, jnp.any(hit, axis=1)


def _every_row(x2: jax.Array, p: dict) -> jax.Array:
    """Every held expert over every row: (E, T, d)."""
    g = L.proj(x2, p["w_gate"], "moe.w_gate")                # (E, T, f)
    u = L.proj(x2, p["w_up"], "moe.w_up")
    return L.proj(jax.nn.silu(g) * u, p["w_down"], "moe.w_down")


def _grouped(x2: jax.Array, p: dict, topw: jax.Array, tope: jax.Array,
             cfg: ModelConfig) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Each routed (row, held expert) pair once, pairs grouped by expert:
    (outputs (P, d), their rows (P,), their gates (P,)).  A row reaches at
    most ``min(top_k, held)`` held experts, so P = that many per row holds
    every pair: none is dropped."""
    m = cfg.moe
    t, k = tope.shape
    local = tope - m.first_expert
    mine = (local >= 0) & (local < m.held)
    key = jnp.where(mine, local, m.held).reshape(-1)        # absent: last
    order = jnp.argsort(key, stable=True)[: t * min(k, m.held)]
    sizes = jnp.bincount(key, length=m.held + 1)[: m.held].astype(jnp.int32)
    rows = order // k

    def rd(a, w, role):
        with jax.named_scope(role):
            return jax.lax.ragged_dot(a, w.astype(COMPUTE_DTYPE), sizes)
    xs = x2[rows]
    h = jax.nn.silu(rd(xs, p["w_gate"], "moe.w_gate")) \
        * rd(xs, p["w_up"], "moe.w_up")
    o = rd(h, p["w_down"], "moe.w_down")        # pairs past the groups: 0
    return o, rows, jnp.where(mine, topw, 0.0).reshape(-1)[order]


def moe_block(x: jax.Array, p: dict, cfg: ModelConfig
              ) -> tuple[jax.Array, jax.Array]:
    """x (..., d) → (the held experts' part (..., d), rows routed to each
    held expert (held,) int32)."""
    lead, d = x.shape[:-1], x.shape[-1]
    x2 = x.reshape(-1, d)
    with jax.named_scope("moe.route"):
        topw, tope = _scores(x2, p["router"], cfg)
        gates, routed = _held(topw, tope, cfg)
    grouped = x2.shape[0] > ROW_TILE_CAP and not L.proj_hooked()
    with jax.named_scope("moe.experts"):
        if grouped:
            o, rows, gate = _grouped(x2, p, topw, tope, cfg)
        else:
            o = _every_row(x2, p)
    with jax.named_scope("moe.combine"):
        if grouped:
            y = jnp.zeros(x2.shape, jnp.float32).at[rows].add(
                o.astype(jnp.float32) * gate[:, None])
        else:
            y = jnp.einsum("etd,te->td", o.astype(jnp.float32), gates)
    return (y.astype(x.dtype).reshape(*lead, d),
            jnp.sum(routed, axis=0, dtype=jnp.int32))
