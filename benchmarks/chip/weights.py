"""Seeded random weights of a dense GQA stack, made on the device.

The benchmark makes the weights, so that the reference can make them again
from the seed and take nothing from the program.  Every projection weight
is sparse at the configuration's density: exactly ``floor(blocks ·
density)`` of its blocks are kept, chosen at random, at the block shape the
configuration gives that projection (``sparsity.mask_blocks``, in ``(in,
out)`` order).  The structure is data, never the program's choice: a plan
that serves these weights at other blocks prunes some non-zeros away, and
the harness's ``nnz_short`` check sees it.
Weights are float32, the type they are served in, scaled so that a
projection keeps its input's variance: ``N(0, 1 / (fan_in · density))``.

Layout: one dict of arrays with a leading layer axis (``wq`` … ``w_down``,
``ln1``, ``ln2``) plus ``embed`` (vocab, d_model) and ``final_norm``.
Projections are ``(in, out)``; norms scale by ``1 + w``; the head is tied to
the embedding.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

#: role → (leaf, in-dim, out-dim) in terms of the configuration's dims.
ROLES = {
    "attn.wq": ("wq", "d_model", "q_width"),
    "attn.wk": ("wk", "d_model", "kv_width"),
    "attn.wv": ("wv", "d_model", "kv_width"),
    "attn.wo": ("wo", "q_width", "d_model"),
    "ffn.w_gate": ("w_gate", "d_model", "d_ff"),
    "ffn.w_up": ("w_up", "d_model", "d_ff"),
    "ffn.w_down": ("w_down", "d_ff", "d_model"),
}


def role_shapes(dims: dict) -> dict[str, tuple[int, int]]:
    ext = dict(dims, q_width=dims["n_heads"] * dims["head_dim"],
               kv_width=dims["n_kv_heads"] * dims["head_dim"])
    return {role: (ext[a], ext[b]) for role, (_, a, b) in ROLES.items()}


def seed32(seed: int, stream: int) -> int:
    """A 32-bit key for ``stream`` of the run's ``seed`` (any size)."""
    return int(np.random.SeedSequence([seed, stream]).generate_state(1)[0])


def masks(cfg: dict, dims: dict) -> dict[str, tuple[int, int]]:
    """The block shape ``(bn, bk)`` of each role's mask, from the
    configuration; each has to tile its weight."""
    blocks = cfg["sparsity"]["mask_blocks"]
    out = {}
    for role, (n, k) in role_shapes(dims).items():
        bn, bk = blocks[role]
        if n % bn or k % bk:
            raise ValueError(f"{role}: mask block {bn}x{bk} does not tile "
                             f"its {n}x{k} weight")
        out[role] = (bn, bk)
    return out


def nnz_per_layer(dims: dict, masks: dict, density: float) -> dict[str, int]:
    """Non-zero weights of each role in one layer."""
    return {role: _keep(n // masks[role][0], k // masks[role][1], density)
            * masks[role][0] * masks[role][1]
            for role, (n, k) in role_shapes(dims).items()}


def nnz_blocks(dims: dict, masks: dict, density: float) -> dict[str, int]:
    """Non-zero blocks per layer of each role."""
    return {role: _keep(n // masks[role][0], k // masks[role][1], density)
            for role, (n, k) in role_shapes(dims).items()}


def _keep(gn: int, gk: int, density: float) -> int:
    return max(int(gn * gk * density), 1)


def _mask(key, layers: int, n: int, k: int, block: tuple, density: float):
    a, b = block
    gn, gk = n // a, k // b
    u = jax.random.uniform(key, (layers, gn * gk))
    rank = jnp.argsort(jnp.argsort(u, axis=1), axis=1)
    keep = (rank < _keep(gn, gk, density)).reshape(layers, gn, 1, gk, 1)
    return jnp.broadcast_to(keep, (layers, gn, a, gk, b)).reshape(
        layers, n, k)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _make(key, dims_t: tuple, masks_t: tuple, density: float) -> dict:
    dims, masks = dict(dims_t), dict(masks_t)
    layers, d, vocab = dims["n_layers"], dims["d_model"], dims["vocab"]
    keys = iter(jax.random.split(key, 4 + 2 * len(ROLES)))
    normal = jax.random.normal
    w = {"embed": normal(next(keys), (vocab, d)) / math.sqrt(d),
         "final_norm": 0.1 * normal(next(keys), (d,)),
         "ln1": 0.1 * normal(next(keys), (layers, d)),
         "ln2": 0.1 * normal(next(keys), (layers, d))}
    for role, (n, k) in role_shapes(dims).items():
        kw, km = next(keys), next(keys)
        dense = normal(kw, (layers, n, k)) / math.sqrt(n * density)
        w[ROLES[role][0]] = dense * _mask(km, layers, n, k, masks[role],
                                          density)
    return w


def make(seed: int, dims: dict, masks: dict, density: float) -> dict:
    """The seed's weights, float32, on the default device, in one call."""
    key = jax.random.key(seed32(seed, 0))
    return _make(key, tuple(sorted(dims.items())),
                 tuple(sorted(masks.items())), float(density))


def program_tree(w: dict) -> dict:
    """``w`` nested as :meth:`repro.models.transformer.Model.init` lays out
    a uniform dense stack."""
    return {"embed": w["embed"], "final_norm": w["final_norm"],
            "blocks": {"ln1": w["ln1"], "ln2": w["ln2"],
                       "attn": {k: w[k] for k in ("wq", "wk", "wv", "wo")},
                       "ffn": {k: w[k] for k in ("w_gate", "w_up",
                                                 "w_down")}}}
