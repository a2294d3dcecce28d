"""A uniform dense GQA decoder stack: everything of the harness that
depends on the architecture, for configurations with ``"arch": "gqa"``.

The harness finds this module by the configuration's ``arch`` key
(``spec.arch``).  It provides:

* ``DIMS`` and ``dims(cfg)``: the dims the harness and the reference use;
* ``program_config(cfg, dims)``: the program's ``ModelConfig``;
* ``roles(dims)``: every projection as ``{role: (in, out, fanout)}``;
* ``make(seed, dims, masks, density)`` and ``program_tree(w)``: the seeded
  weights and the program's layout of them;
* ``logits`` and ``CONTROL_DTYPES``: the float32 reference
  (``reference/gqa.py``) and the dtype each of its controls serves;
* ``decode_flops(ctx)``, ``decode_kv_bytes(ctx)`` and
  ``step_weight_bytes(ctx)``: the work of a traced run's decode, from the
  run's context.

Weights: one dict of arrays with a leading layer axis (``wq`` …
``w_down``, ``ln1``, ``ln2``) plus ``embed`` (vocab, d_model) and
``final_norm``.  Projections are ``(in, out)``, each sparse at the
configuration's mask blocks (``weights.mask``) and scaled so that it keeps
its input's variance, ``N(0, 1 / (fan_in · density))``; norms scale by
``1 + w``; the head is tied to the embedding.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import numpy as np

import counts
import weights
from reference.gqa import CONTROL_DTYPES, logits  # noqa: F401

#: The configuration's dims, as the harness and the reference use them.
DIMS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff",
        "vocab", "rope_fraction", "rope_base", "norm_eps")

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


def dims(cfg: dict) -> dict:
    """The configuration's dims: each entry of ``cfg["dims"]`` names a key
    of the published config or gives the number itself."""
    out = {}
    for k in DIMS:
        v = cfg["dims"][k]
        out[k] = cfg[v] if isinstance(v, str) else v
    return out


def program_config(cfg: dict, dims: dict):
    """The program's configuration at these dims.  The program and the
    reference both tie the head to the embedding, so a configuration that
    unties it is refused rather than served tied."""
    from repro.configs import get_config
    if not cfg["tie_word_embeddings"]:
        raise ValueError(
            f"{cfg['name']}: tie_word_embeddings is false, but the program "
            f"and the reference both tie the head to the embedding")
    base = get_config(cfg["program_arch"])
    return dataclasses.replace(
        base, n_layers=dims["n_layers"], d_model=dims["d_model"],
        n_heads=dims["n_heads"], n_kv_heads=dims["n_kv_heads"],
        d_head=dims["head_dim"], d_ff=dims["d_ff"], vocab=dims["vocab"],
        rope_fraction=dims["rope_fraction"], rope_base=dims["rope_base"],
        norm_eps=dims["norm_eps"], tie_embeddings=True)


def roles(dims: dict) -> dict[str, tuple[int, int, int]]:
    """Each projection's ``(in, out, fanout)``; every one is a plain
    projection (fanout 1)."""
    ext = dict(dims, q_width=dims["n_heads"] * dims["head_dim"],
               kv_width=dims["n_kv_heads"] * dims["head_dim"])
    return {role: (ext[a], ext[b], 1) for role, (_, a, b) in ROLES.items()}


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
    for role, (n, k, _) in roles(dims).items():
        kw, km = next(keys), next(keys)
        dense = normal(kw, (layers, n, k)) / math.sqrt(n * density)
        w[ROLES[role][0]] = dense * weights.mask(km, layers, n, k,
                                                 masks[role], density)
    return w


def make(seed: int, dims: dict, masks: dict, density: float) -> dict:
    """The seed's weights, float32, on the default device, in one call."""
    key = jax.random.key(weights.seed32(seed, 0))
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


def decode_token_flops(dims: dict, nnz_per_layer: int, ctx) -> float:
    """FLOPs that decoding tokens at context lengths ``ctx`` requires,
    whatever serves them: 2 × the non-zero projection weights of every
    layer, the tied head (2 · vocab · d_model), and attention over the live
    context (QKᵀ and PV: 4 · heads · head_dim · ctx per layer)."""
    ctx = np.asarray(ctx, np.float64)
    per_token = 2.0 * dims["n_layers"] * nnz_per_layer \
        + 2.0 * dims["vocab"] * dims["d_model"]
    attn = 4.0 * dims["n_layers"] * dims["n_heads"] * dims["head_dim"]
    return float(per_token * ctx.size + attn * ctx.sum())


def decode_flops(ctx: dict) -> float:
    """FLOPs the window's decoded tokens required, each at its live
    context (``rec.ctx``)."""
    return decode_token_flops(ctx["dims"], ctx["nnz_layer"], ctx["rec"].ctx)


def decode_kv_bytes(ctx: dict) -> float:
    """Bytes of K and V the window's decoded tokens read: every layer's
    whole live context (``rec.ctx``) for each."""
    return ctx["kv_bytes_per_position"] * sum(ctx["rec"].ctx)


def step_weight_bytes(ctx: dict) -> float:
    """Bytes of served weights one decode step must read: the non-zero
    payload and metadata of every kernel-served role in every layer, the
    whole array of every role served dense, and the tied head."""
    kernel_roles, params = ctx["kernel_roles"], ctx["params"]
    out = sum(ctx["dims"]["n_layers"]
              * counts.bitmap_call_cost(r, m=0, x_itemsize=0)[1]
              for r in kernel_roles)
    kernel = {r.role for r in kernel_roles}
    for role in ctx["stacked"].roles:
        if role not in kernel:
            group, leaf = role.split(".", 1)
            w = params["blocks"]["attn" if group == "attn" else "ffn"][leaf]
            out += w.size * w.dtype.itemsize
    return float(out + params["embed"].size * params["embed"].dtype.itemsize)
