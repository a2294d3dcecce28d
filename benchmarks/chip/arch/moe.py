"""A routed-experts decoder stack with windowed and full attention layers,
at one chip's share of the experts: everything of the harness that depends
on the architecture, for configurations with ``"arch": "moe"``.

The harness finds this module by the configuration's ``arch`` key
(``spec.arch``).  It provides what ``arch/gqa.py`` provides (see there);
here:

* ``dims(cfg)``: the widths, ``n_experts`` (the router's outputs, as
  published), ``n_held`` / ``first_expert`` (the experts this chip holds),
  ``top_k``, per layer its ``windows`` (0: full) and ``rope``
  parameters, from ``layer_types`` and ``rope_parameters``, and the
  ``activations`` dtype of ``precision``, at which the reference computes;
* ``roles(dims)``: the attention projections (fanout 1) and the held
  experts' ``moe.w_gate`` / ``moe.w_up`` / ``moe.w_down`` (fanout
  ``n_held``).  The router (d_model × n_experts) is no role: it is served
  dense and unpruned;
* the counts of a traced run's decode (``decode_flops``,
  ``decode_kv_bytes``, ``step_weight_bytes``).

Weights: one dict of arrays with a leading layer axis (``wq`` … ``wo``,
``router``, ``ln1``, ``ln2``, and ``w_gate`` / ``w_up`` / ``w_down`` with
an expert axis after it) plus ``embed`` and ``head`` (vocab, d_model) and
``final_norm``.  Projections are ``(in, out)``, each sparse at the
configuration's mask blocks (``weights.mask``; every held expert its own
mask) and scaled to keep the input's variance, ``N(0, 1 / (fan_in ·
density))``; the router is dense, ``N(0, 1 / d_model)``; norms scale by
``1 + w``.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import numpy as np

import weights
from reference.moe import CONTROL_DTYPES, logits  # noqa: F401

#: Dims that name a key of the published config or give the number.
DIMS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
        "d_expert", "n_experts", "n_held", "first_expert", "top_k",
        "norm_topk_prob", "vocab", "norm_eps", "window")

#: role → (leaf, in-dim, out-dim, fanned out over the held experts).
ROLES = {
    "attn.wq": ("wq", "d_model", "q_width", False),
    "attn.wk": ("wk", "d_model", "kv_width", False),
    "attn.wv": ("wv", "d_model", "kv_width", False),
    "attn.wo": ("wo", "q_width", "d_model", False),
    "moe.w_gate": ("w_gate", "d_model", "d_expert", True),
    "moe.w_up": ("w_up", "d_model", "d_expert", True),
    "moe.w_down": ("w_down", "d_expert", "d_model", True),
}


def dims(cfg: dict) -> dict:
    """The configuration's dims (each entry of ``cfg["dims"]`` names a key
    of the published config or gives the value), with each layer's window
    and RoPE parameters ``(θ, factor, original length, β_fast, β_slow,
    attention factor)`` as tuples, and the dtype the configuration holds
    activations in (``activations``), at which the reference computes."""
    out = {}
    for k in DIMS:
        v = cfg["dims"][k]
        out[k] = cfg[v] if isinstance(v, str) else v
    kinds = cfg["layer_types"][: out["n_layers"]]
    out["windows"] = tuple(out["window"] if k == "sliding_attention" else 0
                           for k in kinds)
    out["rope"] = tuple(_rope(cfg["rope_parameters"][k]) for k in kinds)
    out["activations"] = cfg["precision"]["activations"]
    return out


def _rope(p: dict) -> tuple:
    if p["rope_type"] == "default":
        return (float(p["rope_theta"]), 1.0, 0, 0.0, 0.0, 1.0)
    if p["rope_type"] != "yarn":
        raise ValueError(f"unknown rope_type {p['rope_type']!r}")
    return (float(p["rope_theta"]), float(p["factor"]),
            int(p["original_max_position_embeddings"]),
            float(p["beta_fast"]), float(p["beta_slow"]),
            float(p["attention_factor"]))


def program_config(cfg: dict, dims: dict):
    """The program's configuration at these dims: the held experts, the
    period of attention kinds, and an untied head."""
    from repro.configs import get_config
    from repro.configs.base import AttnKind, MoECfg, RopeCfg

    def kind(window: int, rope: tuple) -> AttnKind:
        theta, factor, orig, b_fast, b_slow, att = rope
        return AttnKind(window=window, rope=RopeCfg(
            theta=theta, factor=factor, original_max=orig, beta_fast=b_fast,
            beta_slow=b_slow, attention_factor=att))
    base = get_config(cfg["program_arch"])
    return dataclasses.replace(
        base, n_layers=dims["n_layers"], d_model=dims["d_model"],
        n_heads=dims["n_heads"], n_kv_heads=dims["n_kv_heads"],
        d_head=dims["head_dim"], d_ff=dims["d_expert"], vocab=dims["vocab"],
        norm_eps=dims["norm_eps"],
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        moe=MoECfg(n_experts=dims["n_experts"], top_k=dims["top_k"],
                   d_expert=dims["d_expert"],
                   norm_topk_prob=dims["norm_topk_prob"],
                   first_expert=dims["first_expert"],
                   n_held=dims["n_held"]),
        attn_pattern=tuple(kind(w, r) for w, r in zip(dims["windows"],
                                                      dims["rope"])))


def _ext(dims: dict) -> dict:
    return dict(dims, q_width=dims["n_heads"] * dims["head_dim"],
                kv_width=dims["n_kv_heads"] * dims["head_dim"])


def roles(dims: dict) -> dict[str, tuple[int, int, int]]:
    """Each projection's ``(in, out, fanout)``: the held experts' roles fan
    out over ``n_held``."""
    ext = _ext(dims)
    return {role: (ext[a], ext[b], dims["n_held"] if fan else 1)
            for role, (_, a, b, fan) in ROLES.items()}


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _make(key, dims_t: tuple, masks_t: tuple, density: float) -> dict:
    dims, masks = dict(dims_t), dict(masks_t)
    layers, d, vocab = dims["n_layers"], dims["d_model"], dims["vocab"]
    keys = iter(jax.random.split(key, 6 + 2 * len(ROLES)))
    normal = jax.random.normal
    w = {"embed": normal(next(keys), (vocab, d)) / math.sqrt(d),
         "head": normal(next(keys), (vocab, d)) / math.sqrt(d),
         "final_norm": 0.1 * normal(next(keys), (d,)),
         "ln1": 0.1 * normal(next(keys), (layers, d)),
         "ln2": 0.1 * normal(next(keys), (layers, d)),
         "router": normal(next(keys), (layers, d, dims["n_experts"]))
         / math.sqrt(d)}
    for role, (n, k, fan) in roles(dims).items():
        kw, km = next(keys), next(keys)
        lead = (layers, fan) if ROLES[role][3] else (layers,)
        dense = normal(kw, lead + (n, k)) / math.sqrt(n * density)
        mask = weights.mask(km, math.prod(lead), n, k, masks[role], density)
        w[ROLES[role][0]] = dense * mask.reshape(dense.shape)
    return w


def make(seed: int, dims: dict, masks: dict, density: float) -> dict:
    """The seed's weights, float32, on the default device, in one call."""
    key = jax.random.key(weights.seed32(seed, 0))
    return _make(key, tuple(sorted(dims.items())),
                 tuple(sorted(masks.items())), float(density))


def program_tree(w: dict) -> dict:
    """``w`` nested as :meth:`repro.models.transformer.Model.init` lays out
    a routed-experts stack with an untied head."""
    return {"embed": w["embed"], "final_norm": w["final_norm"],
            "head": w["head"],
            "blocks": {"ln1": w["ln1"], "ln2": w["ln2"],
                       "attn": {k: w[k] for k in ("wq", "wk", "wv", "wo")},
                       "ffn": {k: w[k] for k in ("router", "w_gate", "w_up",
                                                 "w_down")}}}


def routed_experts(dims: dict) -> float:
    """Held experts a token is expected to reach: ``top_k · n_held /
    n_experts`` (2 at 8 of 64 with 16 held), as uniform routing by random
    weights gives."""
    return dims["top_k"] * dims["n_held"] / dims["n_experts"]


def decode_token_flops(dims: dict, nnz: dict, ctx) -> float:
    """FLOPs that decoding tokens at context lengths ``ctx`` requires,
    whatever serves them.  Per token and layer: 2 × the attention
    projections' non-zeros; 2 × the non-zeros of the held experts it is
    expected to reach (:func:`routed_experts`; ``nnz`` counts all
    ``n_held``); the router at its full width (2 · d_model · n_experts);
    attention over ``min(ctx, window)`` keys on a windowed layer and ``ctx``
    on a full one (QKᵀ and PV: 4 · heads · head_dim per key).  Per token,
    the head over the vocabulary held here (2 · vocab · d_model)."""
    ctx = np.asarray(ctx, np.float64)
    attn_nnz = sum(v for r, v in nnz.items() if r.startswith("attn."))
    expert_nnz = sum(v for r, v in nnz.items() if r.startswith("moe."))
    per_layer = 2.0 * attn_nnz \
        + 2.0 * expert_nnz / dims["n_held"] * routed_experts(dims) \
        + 2.0 * dims["d_model"] * dims["n_experts"]
    per_token = dims["n_layers"] * per_layer \
        + 2.0 * dims["vocab"] * dims["d_model"]
    keys = sum(np.minimum(ctx, w).sum() if w else ctx.sum()
               for w in dims["windows"])
    return float(per_token * ctx.size
                 + 4.0 * dims["n_heads"] * dims["head_dim"] * keys)


def decode_flops(ctx: dict) -> float:
    """FLOPs the window's decoded tokens required, each at its live
    context (``rec.ctx``), from the drawn non-zeros of every role."""
    nnz = {r.role: r.nnzb * r.bn * r.bk for r in ctx["kernel_roles"]}
    return decode_token_flops(ctx["dims"], nnz, ctx["rec"].ctx)


def decode_kv_bytes(ctx: dict) -> float:
    """Bytes of K and V the window's decoded tokens read: on each layer the
    keys it may see, ``min(ctx, window)`` on a windowed layer and ``ctx``
    on a full one."""
    d = ctx["dims"]
    per_layer = ctx["kv_bytes_per_position"] / d["n_layers"]
    c = np.asarray(ctx["rec"].ctx, np.float64)
    return float(per_layer * sum(np.minimum(c, w).sum() if w else c.sum()
                                 for w in d["windows"]))


def step_weight_bytes(ctx: dict) -> float:
    """Bytes of served weights one decode step must read: the non-zero
    payload and the metadata of every kernel-served role in every layer,
    every held expert included (a step's rows reach them all), the router
    and the head.  Each role must be served by the kernel."""
    stacked, params = ctx["stacked"], ctx["params"]
    kernel = {r.role: r for r in ctx["kernel_roles"]}
    out = 0.0
    for role, sr in stacked.roles.items():
        if role not in kernel:
            raise ValueError(f"{role} is not served by the kernel")
        r, data = kernel[role], sr.data
        out += ctx["dims"]["n_layers"] * r.nnzb * (
            r.bn * r.bk * r.payload_itemsize + data["row_ids"].dtype.itemsize)
        out += sum(data[m].size * data[m].dtype.itemsize
                   for m in ("counts", "offsets"))
    router, head = params["blocks"]["ffn"]["router"], params["head"]
    return float(out + router.size * router.dtype.itemsize
                 + head.size * head.dtype.itemsize)
