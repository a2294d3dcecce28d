"""Model assembly for all assigned families.

Design rules:
  * layers are STACKED and consumed by ``jax.lax.scan`` — HLO is O(1) in
    depth (62-layer models compile in seconds, not minutes);
  * heterogeneous stacks (Gemma3 5:1 local:global, RecurrentGemma 1:2
    attn:recurrent) scan over SUPER-BLOCKS whose bodies apply the exact
    interleave, with a small tail stack for the remainder;
  * every train-mode layer body is wrapped in ``jax.checkpoint`` (remat) so
    activation memory is O(layers · boundary), not O(layers · internals);
  * decode carries stacked caches (KV rings for local attention, full KV for
    global, SSM/LRU states) and updates them functionally via scan outputs.

The public surface is :class:`Model` (init / loss / prefill / decode_step /
init_cache) + :func:`input_specs`.

Execution-plane integration: every FFN/attention projection einsum routes
through :func:`repro.models.layers.proj` (a per-role dispatch point).  The
dense model runs it hook-free; :class:`repro.exec.dispatch.CompressedModel`
installs a hook and drives the SAME scanned stack with an ``extras`` pytree
(layer-stacked compressed operands, leading axis = layer): the scan body
installs each layer's slice via :func:`repro.models.layers.layer_ctx`, so
planned projections resolve their per-layer payloads from inside ONE
compiled scanned block instead of a per-layer Python re-drive.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from jax.ad_checkpoint import checkpoint_name

from repro.configs.base import ModelConfig, ShapeCfg
from repro.models import attention as attn
from repro.models import optflags
from repro.models import layers as L
from repro.models import moe as moe_mod
from repro.models import rglru as rg
from repro.models import ssm as ssm_mod
from repro.models.sharding import shard

PyTree = Any


def _ckpt(fn):
    """Remat wrapper.  With 'saveremat', tensors named 'ar_out' (the
    post-all-reduce block outputs) are SAVED, so the backward pass never
    replays TP collectives — Megatron-style selective recompute."""
    if optflags.enabled("saveremat"):
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.save_only_these_names("ar_out"))
    return jax.checkpoint(fn)


# ---------------------------------------------------------------------------
# Per-kind layer params
# ---------------------------------------------------------------------------

def _layer_params(key, cfg: ModelConfig, kind: str) -> dict:
    ks = jax.random.split(key, 3)
    d = cfg.d_model
    p: dict = {"ln1": jnp.zeros((d,), jnp.float32),
               "ln2": jnp.zeros((d,), jnp.float32)}
    if kind in ("attn", "local", "global", "cross"):
        p["attn"] = L.attn_params(ks[0], cfg)
        if kind == "cross":
            p["cross"] = L.attn_params(ks[2], cfg)
            p["ln3"] = jnp.zeros((d,), jnp.float32)
        if cfg.moe:
            p["ffn"] = moe_mod.moe_params(ks[1], cfg)
        elif optflags.enabled("sparseffn") and cfg.sparse_ffn:
            p["ffn"] = L.sparse_mlp_params(ks[1], cfg)
        else:
            p["ffn"] = L.mlp_params(ks[1], cfg)
    elif kind == "rec":
        p["rec"] = rg.rglru_params(ks[0], cfg)
        p["ffn"] = L.mlp_params(ks[1], cfg)
    elif kind == "ssm":
        p["ssm"] = ssm_mod.ssm_params(ks[0], cfg)
        del p["ln2"]
    else:
        raise ValueError(kind)
    return p


def _stack(key, n: int, make) -> PyTree:
    """Stack n independently-initialized param pytrees along axis 0."""
    keys = jax.random.split(key, n)
    trees = [make(k) for k in keys]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


#: Families the cache/decode machinery knows how to serve.  Anything else
#: must fail LOUDLY: the decode-path switches below all end in a default
#: branch, so an unknown family would otherwise silently get the uniform
#: dense cache and mis-serve instead of raising.
KNOWN_FAMILIES = ("dense", "moe", "vlm", "ssm", "hybrid", "encdec")


def _check_family(cfg: ModelConfig) -> None:
    if cfg.family not in KNOWN_FAMILIES:
        raise ValueError(
            f"unknown model family {cfg.family!r} for {cfg.name}: cannot "
            f"build a decode cache (known: {', '.join(KNOWN_FAMILIES)})")


def _uniform_stack(cfg: ModelConfig) -> bool:
    """True when the model is one homogeneous scanned attention stack (the
    families prefill / extras-threading support)."""
    return cfg.family in ("dense", "moe", "vlm") and cfg.hybrid is None


# ---------------------------------------------------------------------------
# Forward bodies (train/prefill mode)
# ---------------------------------------------------------------------------

def _ffn_apply(x, p, cfg: ModelConfig):
    if cfg.moe:
        return moe_mod.moe_block(x, p, cfg)[0]
    return L.mlp(x, p)


def _layer_attn(lx, freqs, window):
    """(freqs, rope scale, window) of the scanned layer: from its slice of
    :func:`repro.models.layers.layer_attn_xs`, or the stack's own."""
    if lx is None:
        return freqs, None, window
    return lx["freqs"], lx["rope_scale"], lx["window"]


def _seqpar(x):
    """Sequence-parallel residual stream (optflag 'seqpar'): shard S on the
    model axis between blocks — XLA then lowers the TP psum as
    reduce-scatter and re-gathers at the next projection."""
    if optflags.enabled("seqpar") and x.ndim == 3 and x.shape[1] % 16 == 0:
        return shard(x, "batch", "model", None)
    return x


def _attn_layer(x, p, cfg, freqs, positions, *, causal=True, window=0,
                kv_override=None, return_kv=False, rope_scale=None):
    h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    a = attn.attention_block(h, p["attn"], cfg, freqs, positions,
                             causal=causal, window=window,
                             return_kv=return_kv, rope_scale=rope_scale)
    if return_kv:
        a, kv_k, kv_v = a
    x = x + checkpoint_name(a, "ar_out")
    x = _seqpar(x)
    if kv_override is not None:
        h = L.rms_norm(x, p["ln3"], cfg.norm_eps)
        x = x + attn.attention_block(h, p["cross"], cfg, None, positions,
                                     causal=False, kv_override=kv_override)
    h = L.rms_norm(x, p["ln2"], cfg.norm_eps)
    out = _seqpar(x + checkpoint_name(_ffn_apply(h, p["ffn"], cfg),
                                      "ar_out"))
    if return_kv:
        return out, kv_k, kv_v
    return out


def _rec_layer(x, p, cfg):
    h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    y, h_last, conv_tail = rg.rglru_block(h, p["rec"], cfg)
    x = x + y
    h = L.rms_norm(x, p["ln2"], cfg.norm_eps)
    return x + L.mlp(h, p["ffn"]), (h_last, conv_tail)


def _ssm_layer(x, p, cfg):
    h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    y, state = ssm_mod.ssm_block(h, p["ssm"], cfg)
    return x + y, state


# ---------------------------------------------------------------------------
# Stack runners (scan over stacked layer params)
# ---------------------------------------------------------------------------

def _run_uniform(x, stacked, cfg: ModelConfig, freqs, positions, kind: str,
                 remat: bool, window: int = 0, extras=None):
    """Scan the uniform layer stack.

    ``extras`` is an optional pytree with a leading layer axis (e.g. a
    :class:`repro.exec.compress.StackedStore`'s payloads).  It rides the
    scan's xs; the body publishes each layer's slice through
    ``L.layer_ctx`` so the proj hook can resolve layer-varying operands
    while the compiled graph stays one scanned block.  A stack with an
    ``attn_pattern`` also scans each layer's window and RoPE
    (:func:`repro.models.layers.layer_attn_xs`)."""
    causal = cfg.family != "encdec" or kind != "enc"
    layer_xs = L.layer_attn_xs(cfg) if kind == "attn" else None

    def body(h, sl):
        p, e, lx = sl
        fr, scale, win = _layer_attn(lx, freqs, window)
        with L.layer_ctx(e):
            if kind == "ssm":
                out, _ = _ssm_layer(h, p, cfg)
            else:
                out = _attn_layer(h, p, cfg, fr, positions,
                                  causal=causal, window=win,
                                  rope_scale=scale)
        return out, None

    fn = _ckpt(body) if remat else body
    x, _ = jax.lax.scan(fn, x, (stacked, extras, layer_xs))
    return x


def _run_gemma3(x, params, cfg: ModelConfig, freqs_l, freqs_g, positions,
                remat: bool):
    """10×(5 local + 1 global) + 2 local."""
    def super_block(h, p):
        def local_body(hh, pp):
            return _attn_layer(hh, pp, cfg, freqs_l, positions, causal=True,
                               window=cfg.window), None

        def global_body(hh, pp):
            return _attn_layer(hh, pp, cfg, freqs_g, positions, causal=True)

        lb = _ckpt(local_body) if remat else local_body
        h, _ = jax.lax.scan(lb, h, p["local"])
        gb = _ckpt(global_body) if remat else global_body
        h = gb(h, p["global"])
        return h, None

    x, _ = jax.lax.scan(super_block, x, params["super"])
    def tail_body(hh, pp):
        return _attn_layer(hh, pp, cfg, freqs_l, positions, causal=True,
                           window=cfg.window), None
    tb = _ckpt(tail_body) if remat else tail_body
    x, _ = jax.lax.scan(tb, x, params["tail"])
    return x


def _run_recurrentgemma(x, params, cfg: ModelConfig, freqs, positions,
                        remat: bool):
    """8×(rec, rec, attn) + 2 rec."""
    def super_block(h, p):
        h, _ = _rec_layer(h, p["rec1"], cfg)
        h, _ = _rec_layer(h, p["rec2"], cfg)
        h = _attn_layer(h, p["attn"], cfg, freqs, positions, causal=True,
                        window=cfg.window)
        return h, None

    sb = _ckpt(super_block) if remat else super_block
    x, _ = jax.lax.scan(sb, x, params["super"])

    def tail(h, p):
        h, _ = _rec_layer(h, p, cfg)
        return h, None
    tl = _ckpt(tail) if remat else tail
    x, _ = jax.lax.scan(tl, x, params["tail"])
    return x


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig

    # ---------------- params ----------------
    def init(self, rng) -> PyTree:
        cfg = self.cfg
        k_emb, k_layers, k_enc, k_tail = jax.random.split(rng, 4)
        params: dict = {
            "embed": L._init(k_emb, (cfg.vocab, cfg.d_model), scale_axis=1),
            "final_norm": jnp.zeros((cfg.d_model,), jnp.float32),
        }
        if not cfg.tie_embeddings:
            params["head"] = L._init(jax.random.fold_in(rng, 5),
                                     (cfg.vocab, cfg.d_model), scale_axis=1)
        if cfg.family in ("dense", "moe", "vlm") and cfg.hybrid is None:
            params["blocks"] = _stack(
                k_layers, cfg.n_layers,
                lambda k: _layer_params(k, cfg, "attn"))
        elif cfg.name.startswith("gemma3"):
            n_super = (cfg.n_layers - len(cfg.hybrid.tail)) // 6
            params["super"] = _stack(k_layers, n_super, lambda k: {
                "local": _stack(jax.random.fold_in(k, 0), 5,
                                lambda kk: _layer_params(kk, cfg, "local")),
                "global": _layer_params(jax.random.fold_in(k, 1), cfg, "global"),
            })
            params["tail"] = _stack(k_tail, len(cfg.hybrid.tail),
                                    lambda k: _layer_params(k, cfg, "local"))
        elif cfg.family == "hybrid":
            n_super = (cfg.n_layers - len(cfg.hybrid.tail)) // 3
            params["super"] = _stack(k_layers, n_super, lambda k: {
                "rec1": _layer_params(jax.random.fold_in(k, 0), cfg, "rec"),
                "rec2": _layer_params(jax.random.fold_in(k, 1), cfg, "rec"),
                "attn": _layer_params(jax.random.fold_in(k, 2), cfg, "attn"),
            })
            params["tail"] = _stack(k_tail, len(cfg.hybrid.tail),
                                    lambda k: _layer_params(k, cfg, "rec"))
        elif cfg.family == "ssm":
            params["blocks"] = _stack(k_layers, cfg.n_layers,
                                      lambda k: _layer_params(k, cfg, "ssm"))
        elif cfg.family == "encdec":
            params["enc_blocks"] = _stack(
                k_enc, cfg.enc_layers, lambda k: _layer_params(k, cfg, "attn"))
            params["blocks"] = _stack(
                k_layers, cfg.n_layers,
                lambda k: _layer_params(k, cfg, "cross"))
            params["enc_norm"] = jnp.zeros((cfg.d_model,), jnp.float32)
        else:
            raise ValueError(cfg.family)
        return params

    # ---------------- forward (train / prefill hidden states) ----------------
    def hidden_states(self, params: PyTree, tokens: jax.Array,
                      enc_frames: Optional[jax.Array] = None,
                      remat: bool = True, extras: PyTree = None) -> jax.Array:
        cfg = self.cfg
        b, s = tokens.shape
        if extras is not None and not _uniform_stack(cfg):
            raise NotImplementedError(
                "extras (layer-stacked operands) need a uniform layer stack")
        x = L.embed(tokens, params["embed"])
        positions = jnp.arange(s)
        freqs = L.rope_freqs(cfg)
        if cfg.family == "encdec":
            assert enc_frames is not None, "encdec needs encoder frames"
            enc = enc_frames.astype(L.COMPUTE_DTYPE) + _sinusoid(
                cfg.enc_seq, cfg.d_model)
            enc = _run_uniform(enc, params["enc_blocks"], cfg, None,
                               jnp.arange(cfg.enc_seq), "enc", remat)
            enc = L.rms_norm(enc, params["enc_norm"], cfg.norm_eps)
            x = x + _sinusoid(s, cfg.d_model)

            def body(h, p):
                return _attn_layer(h, p, cfg, None, positions, causal=True,
                                   kv_override=(enc, enc)), None
            fn = _ckpt(body) if remat else body
            x, _ = jax.lax.scan(fn, x, params["blocks"])
        elif cfg.name.startswith("gemma3"):
            x = _run_gemma3(x, params, cfg, freqs, freqs, positions, remat)
        elif cfg.family == "hybrid":
            x = _run_recurrentgemma(x, params, cfg, freqs, positions, remat)
        elif cfg.family == "ssm":
            x = _run_uniform(x, params["blocks"], cfg, None, positions,
                             "ssm", remat)
        else:
            x = _run_uniform(x, params["blocks"], cfg, freqs, positions,
                             "attn", remat, window=cfg.window, extras=extras)
        return L.rms_norm(x, params["final_norm"], cfg.norm_eps)

    def loss(self, params: PyTree, batch: dict) -> jax.Array:
        x = self.hidden_states(params, batch["tokens"],
                               batch.get("enc_frames"))
        return L.unembed_loss(x, L.head_table(params), batch["labels"])

    # ---------------- decode ----------------
    @jax.named_scope("prefill")
    def prefill(self, params: PyTree, tokens: jax.Array, max_len: int,
                extras: PyTree = None) -> tuple[jax.Array, PyTree]:
        """Full-sequence forward that ALSO fills a fresh decode cache.

        One batched pass replaces the token-by-token decode_step ingest:
        the layer scan's ys carry each layer's post-RoPE, pre-GQA-repeat
        (K, V) — exactly what :func:`attention_decode_block` would have
        written — so ``decode_step(pos=s)`` continues seamlessly.  Returns
        (logits (B, S, V) float32, cache).

        Uniform stacks only, each layer's K/V kept for every position
        (a stack-wide ``window`` ring and hybrid/ssm/encdec states keep the
        token-by-token path — see ``launch.serve.generate``'s fallback); an
        ``attn_pattern``'s windows mask, over the full cache.
        """
        cfg = self.cfg
        if not _uniform_stack(cfg) or cfg.window:
            raise NotImplementedError(
                "prefill: uniform stacks with full-length caches only")
        b, s = tokens.shape
        if s > max_len:
            raise ValueError(f"prompt ({s}) exceeds max_len ({max_len})")
        x = L.embed(tokens, params["embed"])
        positions = jnp.arange(s)
        freqs = L.rope_freqs(cfg)

        def body(h, sl):
            p, e, lx = sl
            fr, scale, win = _layer_attn(lx, freqs, 0)
            with L.layer_ctx(e):
                out, k, v = _attn_layer(h, p, cfg, fr, positions,
                                        causal=True, window=win,
                                        return_kv=True, rope_scale=scale)
            return out, (k, v)

        x, (ks, vs) = jax.lax.scan(
            body, x, (params["blocks"], extras, L.layer_attn_xs(cfg)))
        x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = jnp.einsum("btd,vd->btv", x,
                            L.head_table(params).astype(L.COMPUTE_DTYPE))
        logits = shard(logits.astype(jnp.float32), "batch", None, "vocab")
        cache = self.init_cache(b, max_len)
        dt = cache["self"]["k"].dtype
        cache["self"]["k"] = jax.lax.dynamic_update_slice_in_dim(
            cache["self"]["k"], ks.astype(dt), 0, axis=2)
        cache["self"]["v"] = jax.lax.dynamic_update_slice_in_dim(
            cache["self"]["v"], vs.astype(dt), 0, axis=2)
        return logits, cache

    def init_cache(self, batch: int, max_len: int) -> PyTree:
        """Zeroed decode caches sized for ``max_len`` context."""
        cfg = self.cfg
        _check_family(cfg)
        hd, nk = cfg.head_dim, max(cfg.n_kv_heads, 1)
        dt = L.COMPUTE_DTYPE

        def kv(n_layers, length):
            shape = (n_layers, batch, length, nk, hd)
            return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}

        if cfg.family == "ssm":
            s = cfg.ssm
            d_in = cfg.d_model * s.expand
            nh = d_in // s.head_dim
            conv_c = d_in + 2 * s.d_state
            return {
                "state": jnp.zeros((cfg.n_layers, batch, nh, s.head_dim,
                                    s.d_state), dt),
                "conv": jnp.zeros((cfg.n_layers, batch, s.d_conv - 1,
                                   conv_c), dt),
            }
        if cfg.name.startswith("gemma3"):
            n_super = (cfg.n_layers - 2) // 6
            win = min(cfg.window, max_len)
            return {
                "local": kv(n_super * 5 + 2, win),
                "global": kv(n_super, max_len),
            }
        if cfg.family == "hybrid":
            n_super = (cfg.n_layers - 2) // 3
            dr = cfg.d_model
            win = min(cfg.window, max_len) if cfg.window else max_len
            return {
                "attn": kv(n_super, win),
                "h": jnp.zeros((n_super * 2 + 2, batch, dr), dt),
                "conv": jnp.zeros((n_super * 2 + 2, batch, 3, dr), dt),
            }
        if cfg.family == "encdec":
            return {
                "self": kv(cfg.n_layers, max_len),
                "cross": kv(cfg.n_layers, cfg.enc_seq),
                "cross_ready": jnp.zeros((), jnp.int32),
            }
        cache = {"self": kv(cfg.n_layers, max_len)}
        if cfg.moe:
            # rows the decode steps routed to each held expert, per layer
            # (layer-major): a device-side counter, read only when metrics
            # are collected (``Mixer.expert_rows``)
            cache["moe_rows"] = jnp.zeros((cfg.n_layers * cfg.moe.held,),
                                          jnp.int32)
        return cache

    @jax.named_scope("decode")
    def decode_step(self, params: PyTree, cache: PyTree, tokens: jax.Array,
                    pos: jax.Array, extras: PyTree = None
                    ) -> tuple[jax.Array, PyTree]:
        """One token for the whole batch.  tokens: (B,); pos: scalar for a
        lockstep batch, or a (B,) per-slot position vector (continuous
        batching: each batch row is an independent KV slot decoding at its
        own position — RoPE, cache writes, and the causal mask all follow
        the row's own position; see :mod:`repro.launch.mixer`).  Returns
        (logits (B, V), new cache).  ``extras``: optional layer-stacked
        operand pytree riding the scan (see _run_uniform)."""
        cfg = self.cfg
        _check_family(cfg)
        pos = jnp.asarray(pos)
        if pos.ndim not in (0, 1) or \
                (pos.ndim == 1 and pos.shape[0] != tokens.shape[0]):
            raise ValueError(
                f"decode_step: pos must be a scalar or a per-slot vector "
                f"matching the batch ({tokens.shape[0]},); got {pos.shape}")
        if extras is not None and not _uniform_stack(cfg):
            raise NotImplementedError(
                "extras (layer-stacked operands) need a uniform layer stack")
        x = jnp.take(params["embed"], tokens, axis=0).astype(L.COMPUTE_DTYPE)
        freqs = L.rope_freqs(cfg)

        def attn_step(h, p, kc, vc, cache_pos, lx=None):
            """One layer; returns (h, kc, vc, rows routed to each held
            expert or None)."""
            fr, scale, win = _layer_attn(lx, freqs, None)
            hn = L.rms_norm(h[:, None], p["ln1"], cfg.norm_eps)[:, 0]
            y, kc, vc = attn.attention_decode_block(
                hn, p["attn"], cfg, fr, pos, kc, vc, cache_pos,
                window=win, rope_scale=scale)
            h = h + y
            hn = L.rms_norm(h[:, None], p["ln2"], cfg.norm_eps)[:, 0]
            rows = None
            if cfg.moe:
                y, rows = moe_mod.moe_block(hn, p["ffn"], cfg)
                h = h + y
            elif "payload_gate" in p["ffn"]:
                h = h + L.sparse_mlp_decode(hn, p["ffn"])
            else:
                h = h + L.mlp(hn[:, None], p["ffn"])[:, 0]
            return h, kc, vc, rows

        if cfg.family == "ssm":
            def body(h, sl):
                p, st, cv = sl
                hn = L.rms_norm(h[:, None], p["ln1"], cfg.norm_eps)[:, 0]
                y, st, cv = ssm_mod.ssm_decode(hn, p["ssm"], cfg, st, cv)
                return h + y, (st, cv)
            x, (st, cv) = jax.lax.scan(
                body, x, (params["blocks"], cache["state"], cache["conv"]))
            cache = {"state": st, "conv": cv}
        elif cfg.name.startswith("gemma3"):
            win = cache["local"]["k"].shape[2]
            lpos = jnp.where(win > 0, pos % win, 0)
            n_super = cache["global"]["k"].shape[0]

            def super_body(h, sl):
                p, lk, lv, gk, gv = sl

                def local_body(hh, inner):
                    pp, kk, vv = inner
                    hh, kk, vv, _ = attn_step(hh, pp, kk, vv, lpos)
                    return hh, (kk, vv)
                h, (lk, lv) = jax.lax.scan(
                    local_body, h, (p["local"], lk, lv))
                h, gk, gv, _ = attn_step(h, p["global"], gk, gv, pos)
                return h, (lk, lv, gk, gv)

            lk5 = cache["local"]["k"][: n_super * 5].reshape(
                (n_super, 5) + cache["local"]["k"].shape[1:])
            lv5 = cache["local"]["v"][: n_super * 5].reshape(
                (n_super, 5) + cache["local"]["v"].shape[1:])
            x, (lk5, lv5, gk, gv) = jax.lax.scan(
                super_body, x,
                (params["super"], lk5, lv5,
                 cache["global"]["k"], cache["global"]["v"]))

            def tail_body(h, sl):
                p, kk, vv = sl
                h, kk, vv, _ = attn_step(h, p, kk, vv, lpos)
                return h, (kk, vv)
            tk = cache["local"]["k"][n_super * 5:]
            tv = cache["local"]["v"][n_super * 5:]
            x, (tk, tv) = jax.lax.scan(tail_body, x, (params["tail"], tk, tv))
            cache = {
                "local": {
                    "k": jnp.concatenate(
                        [lk5.reshape((-1,) + lk5.shape[2:]), tk]),
                    "v": jnp.concatenate(
                        [lv5.reshape((-1,) + lv5.shape[2:]), tv])},
                "global": {"k": gk, "v": gv},
            }
        elif cfg.family == "hybrid":
            win = cache["attn"]["k"].shape[2]
            apos = pos % win
            n_super = cache["attn"]["k"].shape[0]

            def rec_step(h, p, hs, cv):
                hn = L.rms_norm(h[:, None], p["ln1"], cfg.norm_eps)[:, 0]
                y, hs, cv = rg.rglru_decode(hn, p["rec"], cfg, hs, cv)
                h = h + y
                hn = L.rms_norm(h[:, None], p["ln2"], cfg.norm_eps)[:, 0]
                return h + L.mlp(hn[:, None], p["ffn"])[:, 0], hs, cv

            def super_body(h, sl):
                p, kk, vv, h1, c1, h2, c2 = sl
                h, h1, c1 = rec_step(h, p["rec1"], h1, c1)
                h, h2, c2 = rec_step(h, p["rec2"], h2, c2)
                h, kk, vv, _ = attn_step(h, p["attn"], kk, vv, apos)
                return h, (kk, vv, h1, c1, h2, c2)

            hs = cache["h"][: 2 * n_super].reshape(
                (n_super, 2) + cache["h"].shape[1:])
            cv = cache["conv"][: 2 * n_super].reshape(
                (n_super, 2) + cache["conv"].shape[1:])
            x, (kk, vv, h1, c1, h2, c2) = jax.lax.scan(
                super_body, x,
                (params["super"], cache["attn"]["k"], cache["attn"]["v"],
                 hs[:, 0], cv[:, 0], hs[:, 1], cv[:, 1]))

            def tail_body(h, sl):
                p, hh, cc = sl
                h, hh, cc = rec_step(h, p, hh, cc)
                return h, (hh, cc)
            x, (th, tc) = jax.lax.scan(
                tail_body, x, (params["tail"], cache["h"][2 * n_super:],
                               cache["conv"][2 * n_super:]))
            new_h = jnp.concatenate(
                [jnp.stack([h1, h2], 1).reshape((-1,) + h1.shape[1:]), th])
            new_c = jnp.concatenate(
                [jnp.stack([c1, c2], 1).reshape((-1,) + c1.shape[1:]), tc])
            cache = {"attn": {"k": kk, "v": vv}, "h": new_h, "conv": new_c}
        elif cfg.family == "encdec":
            def body(h, sl):
                p, kk, vv, ck, cv = sl
                hn = L.rms_norm(h[:, None], p["ln1"], cfg.norm_eps)[:, 0]
                y, kk, vv = attn.attention_decode_block(
                    hn, p["attn"], cfg, freqs, pos, kk, vv, pos)
                h = h + y
                hn = L.rms_norm(h[:, None], p["ln3"], cfg.norm_eps)[:, 0]
                q = jnp.einsum("bd,de->be", hn,
                               p["cross"]["wq"].astype(L.COMPUTE_DTYPE))
                q = q.reshape(-1, cfg.n_heads, cfg.head_dim)
                y = attn.decode_attention(q, ck, cv, ck.shape[1])
                h = h + jnp.einsum(
                    "be,ed->bd", y.reshape(y.shape[0], -1),
                    p["cross"]["wo"].astype(L.COMPUTE_DTYPE))
                hn = L.rms_norm(h[:, None], p["ln2"], cfg.norm_eps)[:, 0]
                h = h + L.mlp(hn[:, None], p["ffn"])[:, 0]
                return h, (kk, vv)
            x, (kk, vv) = jax.lax.scan(
                body, x, (params["blocks"], cache["self"]["k"],
                          cache["self"]["v"], cache["cross"]["k"],
                          cache["cross"]["v"]))
            cache = dict(cache)
            cache["self"] = {"k": kk, "v": vv}
        else:
            def body(h, sl):
                p, kk, vv, e, lx = sl
                cache_pos = pos % kk.shape[1] if cfg.window else pos
                with L.layer_ctx(e):
                    h, kk, vv, rows = attn_step(h, p, kk, vv, cache_pos, lx)
                return h, (kk, vv, rows)
            x, (kk, vv, rows) = jax.lax.scan(
                body, x, (params["blocks"], cache["self"]["k"],
                          cache["self"]["v"], extras, L.layer_attn_xs(cfg)))
            new = {"self": {"k": kk, "v": vv}}
            if rows is not None:
                new["moe_rows"] = cache["moe_rows"] + rows.reshape(-1)
            cache = new

        x = L.rms_norm(x[:, None], params["final_norm"], cfg.norm_eps)[:, 0]
        return L.logits_head(x, L.head_table(params)), cache


def _sinusoid(s: int, d: int) -> jax.Array:
    pos = jnp.arange(s, dtype=jnp.float32)[:, None]
    dim = jnp.arange(0, d, 2, dtype=jnp.float32)[None, :]
    ang = pos / jnp.power(10_000.0, dim / d)
    pe = jnp.zeros((s, d), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(ang))
    pe = pe.at[:, 1::2].set(jnp.cos(ang))
    return pe[None].astype(L.COMPUTE_DTYPE)


# ---------------------------------------------------------------------------
# Input specs (ShapeDtypeStruct stand-ins, no allocation) — dry-run fodder
# ---------------------------------------------------------------------------

def input_specs(cfg: ModelConfig, shape: ShapeCfg) -> dict:
    """Abstract inputs for one (arch × shape) cell.

    train/prefill: token + label batches (+ stub frontend embeddings);
    decode: one-token batch + position.  The KV cache itself is produced by
    ``Model.init_cache`` shapes via eval_shape (no allocation).
    """
    b, s = shape.global_batch, shape.seq_len
    tok = jax.ShapeDtypeStruct((b, s), jnp.int32)
    if shape.kind == "train":
        out = {"tokens": tok, "labels": jax.ShapeDtypeStruct((b, s), jnp.int32)}
        if cfg.family == "encdec":
            out["enc_frames"] = jax.ShapeDtypeStruct(
                (b, cfg.enc_seq, cfg.d_model), jnp.float32)
        return out
    if shape.kind == "prefill":
        out = {"tokens": tok}
        if cfg.family == "encdec":
            out["enc_frames"] = jax.ShapeDtypeStruct(
                (b, cfg.enc_seq, cfg.d_model), jnp.float32)
        return out
    # decode: one new token against a seq_len-deep cache
    return {"tokens": jax.ShapeDtypeStruct((b,), jnp.int32),
            "pos": jax.ShapeDtypeStruct((), jnp.int32)}
