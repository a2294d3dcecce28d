#!/usr/bin/env python3
"""Bring-up smoke run of the compressed serving path on a TPU.

Serves chatglm3-6b at its published widths (d_model 4096, 32 heads, 2 KV
groups, d_ff 13696, vocab 65024), cut in depth to 4 of its 28 layers, with
random weights from a seed, through the library's own entry points:

  plan → prune → compress → stack   repro.launch.serve.compressed_model
  static batch                      repro.launch.serve.generate
  request stream                    repro.launch.mixer.Mixer

and checks what comes out: compressed vs dense last-position prefill logits
on the same pruned weights, every mixer request served in full, every
planned role run as a Mosaic-compiled (not interpreted) sparse kernel with
no role demoted.  The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

  python chip_smoke.py              one chip: set-up, static and mixer phases
  python chip_smoke.py --chips 4    four chips: compressed generate over a
                                    4-way data mesh vs the same batch on one
                                    device, and no other phase

It runs in one process and starts none, so it holds the chip alone.  It
exits non-zero, printing no result, when JAX finds no TPU.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "chatglm3-6b"
LAYERS = 4                  # of the published 28: what 16 GB holds (below)
SEED = 0
BATCH, PROMPT, GEN = 8, 128, 16
SLOTS, MAX_LEN = 8, 1024
MIXER_PROMPTS = (1, 3, 37, 128, 130, 200, 333, 512, 7, 64, 257, 500)
MIXER_NEW = 16

#: Bound on max|compressed - dense| / max|dense| over last-position prefill
#: logits.  Both paths run bf16 activations (8 significant bits: one step is
#: 2^-8 ≈ 3.9e-3 of a value) over the same pruned weights, and differ only
#: in where they round: the dense einsum rounds every weight and projection
#: output to bf16, the kernels keep fp32 payloads and accumulate in f32.  On
#: the CPU at d_model 512-1024 and 1-8 layers each path lands 1.0-1.5e-2 of
#: the largest logit from an fp32 reference, and the two differ by 0.8-1.8e-2
#: (the error grows slowly with width).  5e-2, about 13 bf16 steps of the
#: largest logit, holds that at published widths, while a wrong block, row
#: or column stripe moves the logits by the order of their own scale.
LOGITS_TOL = 5e-2


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(1)


def device_info(chips: int) -> dict:
    """The device as JAX reports it; exits unless it is a TPU and there are
    at least ``chips`` of them."""
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"JAX found no TPU: its first device is on platform "
             f"{dev.platform!r}; this smoke run measures the chip and does "
             f"not fall back")
    if len(devices) < chips:
        fail(f"--chips {chips} needs {chips} devices; JAX sees "
             f"{len(devices)}")
    print(f"device: {dev.device_kind} x{len(devices)}  jax {jax.__version__}",
          flush=True)
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def smoke_config():
    from repro.configs import get_config
    return dataclasses.replace(get_config(ARCH), n_layers=LAYERS)


def print_reckoning(cfg) -> None:
    """The byte count behind the depth cut."""
    per_layer = sum(r.n * r.k * r.fanout for r in cfg.matmul_roles())
    fp32 = 4 * per_layer
    stores = 2 * 0.5 * fp32          # per-layer store + stacked copy, d=0.5
    embed = 4 * cfg.vocab * cfg.d_model
    total = embed + cfg.n_layers * (fp32 + stores)
    print(f"model: {cfg.name} at published widths (d_model {cfg.d_model}, "
          f"{cfg.n_heads} heads, {cfg.n_kv_heads} KV groups, d_ff "
          f"{cfg.d_ff}, vocab {cfg.vocab}); depth {cfg.n_layers} of 28 "
          f"layers", flush=True)
    print(f"  per layer {per_layer / 1e6:.0f} M params = {fp32 / 1e9:.3f} GB "
          f"fp32 + {stores / 1e9:.3f} GB compressed store and stacked copy "
          f"at density 0.5; tied embedding {embed / 1e9:.3f} GB; "
          f"{cfg.n_layers} layers ≈ {total / 1e9:.2f} GB of 16 GB HBM",
          flush=True)


class CompileClock:
    """Sums XLA backend compile time and counts persistent-cache hits."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def build(cfg):
    """Random weights on the device, then plan → prune → compress → stack.
    Returns (compressed model, pruned params)."""
    import jax
    from repro.launch.serve import compressed_model
    from repro.models.transformer import Model

    t0 = time.perf_counter()
    params = Model(cfg).init(jax.random.key(SEED))
    jax.block_until_ready(params)
    t_init = time.perf_counter() - t0
    t0 = time.perf_counter()
    cm, pruned = compressed_model(cfg, params)
    del params                       # the unpruned projections
    jax.block_until_ready(jax.tree.leaves(cm.stacked.extras()))
    t_setup = time.perf_counter() - t0
    plan = cm.store.plan
    stats = jax.devices()[0].memory_stats() or {}
    print(f"set-up: init {t_init:.1f}s, plan+prune+compress+stack "
          f"{t_setup:.1f}s; bytes_in_use {stats.get('bytes_in_use')}",
          flush=True)
    for op in plan.ops:
        ch = op.choice
        print(f"  {op.role:<11} {op.n}x{op.k} kernel={ch.kind} "
              f"block={ch.block_n}x{ch.block_k} format={ch.format_str}",
              flush=True)
    print(f"  fallbacks={plan.fallback_counts() or 'none'} "
          f"achieved_ratio={cm.store.achieved_ratio():.4f}", flush=True)
    check_no_demotion(cm)
    return cm, pruned


def check_no_demotion(cm) -> None:
    """Every planned role serves through its sparse kernel."""
    plan = cm.store.plan
    for op in plan.ops:
        ch = op.choice
        if ch.kind not in ("bitmap", "nm"):
            fail(f"role {op.role} runs {ch.kind} "
                 f"(fallback={ch.fallback}), not a sparse kernel")
        if ch.fallback is not None:
            fail(f"role {op.role} carries fallback {ch.fallback}")
        sr = cm.stacked.roles.get(op.role)
        if sr is None or sr.kind != ch.kind:
            fail(f"role {op.role} was demoted from {ch.kind}")


def last_logits(model, params, prompts, max_len: int, mesh=None):
    """Last-position prefill logits through ``model.prefill`` — the same
    jitted call :func:`repro.launch.serve.generate` makes — on one device or
    with the batch sharded over ``mesh``.  Returned on the host."""
    import jax
    import numpy as np
    from repro.exec.dispatch import jit_serving
    from repro.launch.mesh import axis_map_for
    from repro.models.sharding import logical_axis_rules, named_sharding

    fn = jit_serving(model, functools.partial(model.prefill, max_len=max_len))
    if mesh is None:
        return np.asarray(fn(params, prompts)[0][:, -1])
    with mesh, logical_axis_rules(axis_map_for(mesh), mesh=mesh):
        sharded = jax.device_put(prompts, named_sharding(mesh, "batch", None))
        return np.asarray(fn(params, sharded)[0][:, -1])


def compare_logits(name: str, got, want) -> float:
    import numpy as np
    if not (np.isfinite(got).all() and np.isfinite(want).all()):
        fail(f"{name}: non-finite logits")
    err = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
    print(f"  {name}: max|Δ|/max|ref| = {err!r} (tolerance {LOGITS_TOL})",
          flush=True)
    if not err < LOGITS_TOL:
        fail(f"{name}: logits error {err} exceeds {LOGITS_TOL}")
    return err


def prompts_batch(cfg, batch: int, plen: int):
    import jax.numpy as jnp
    import numpy as np
    rng = np.random.default_rng(SEED)
    return jnp.asarray(rng.integers(0, cfg.vocab, (batch, plen)), jnp.int32)


def static_phase(cm, pruned) -> float:
    """Compressed ``generate`` (batch 8, prompt 128, gen 16, unguarded) and
    compressed-vs-dense prefill logits on the same pruned tree."""
    import numpy as np
    from repro.launch.serve import generate
    from repro.models.transformer import Model

    cfg = cm.cfg
    prompts = prompts_batch(cfg, BATCH, PROMPT)
    max_len = PROMPT + GEN
    toks, t_prefill, t_gen = generate(cm, pruned, prompts, GEN, max_len)
    toks = np.asarray(toks)
    print(f"static: batch {BATCH} prompt {PROMPT} gen {GEN}: prefill "
          f"{t_prefill:.2f}s, decode {t_gen:.2f}s (first calls, compile "
          f"included)", flush=True)
    if toks.shape != (BATCH, GEN) or toks.min() < 0 or toks.max() >= cfg.vocab:
        fail(f"static generate returned {toks.shape} tokens in "
             f"[{toks.min()}, {toks.max()}]")
    comp = last_logits(cm, pruned, prompts, max_len)
    dense = last_logits(Model(cfg), pruned, prompts, max_len)
    return compare_logits("compressed vs dense prefill", comp, dense)


def mixer_phase(cm, pruned) -> None:
    """12 greedy requests of mixed prompt length through an 8-slot mixer."""
    import numpy as np
    from repro.launch.mixer import Mixer, Request

    rng = np.random.default_rng(SEED + 1)
    reqs = [Request(uid=f"req{i}", prompt=rng.integers(0, cm.cfg.vocab, plen),
                    max_new=MIXER_NEW)
            for i, plen in enumerate(MIXER_PROMPTS)]
    mx = Mixer(cm, pruned, slots=SLOTS, max_len=MAX_LEN)
    t0 = time.perf_counter()
    results = mx.run(reqs)
    st = mx.stats()
    print(f"mixer: {len(reqs)} requests, {SLOTS} slots, max_len {MAX_LEN}: "
          f"{st['tokens']} tokens, {st['steps']} decode steps, "
          f"{time.perf_counter() - t0:.1f}s (compile included)", flush=True)
    done = 0
    for req, res in zip(reqs, results):
        ok = (res.n_tokens == MIXER_NEW and len(res.tokens) == MIXER_NEW
              and (res.tokens >= 0).all()
              and not res.report.fallback_counts())
        done += ok
        print(f"  {res.uid}: prompt {len(req.prompt)} -> {res.n_tokens} "
              f"tokens{'' if ok else '  FAILED'}", flush=True)
    print(f"  {done}/{len(reqs)} requests complete with {MIXER_NEW} tokens",
          flush=True)
    if done != len(reqs):
        fail(f"only {done}/{len(reqs)} mixer requests completed")


def replicated(cm, params, mesh):
    """``cm`` and ``params`` with every array replicated over ``mesh``: the
    serving batch is what shards, the weights are whole on every chip."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.exec import CompressedModel

    rep = NamedSharding(mesh, PartitionSpec())
    stacked = dataclasses.replace(cm.stacked, roles={
        role: dataclasses.replace(
            sr, data=None if sr.data is None else jax.device_put(sr.data, rep))
        for role, sr in cm.stacked.roles.items()})
    return (CompressedModel(cm.model, cm.store, stacked),
            jax.device_put(params, rep))


def mesh_phase(cfg) -> float:
    """Compressed ``generate`` with the batch sharded over a 4-way data mesh,
    against the same batch on one device."""
    import numpy as np
    from repro.launch.mesh import make_serve_mesh, mesh_axis_sizes
    from repro.launch.serve import generate

    cm, pruned = build(cfg)
    prompts = prompts_batch(cfg, BATCH, PROMPT)
    max_len = PROMPT + GEN
    one = last_logits(cm, pruned, prompts, max_len)
    toks_one = np.asarray(generate(cm, pruned, prompts, GEN, max_len)[0])
    mesh = make_serve_mesh(BATCH)
    if mesh is None:
        fail("make_serve_mesh found one device")
    print(f"mesh: {mesh_axis_sizes(mesh)}", flush=True)
    # rebinding drops the single-device copies once the replicas exist
    cm, pruned = replicated(cm, pruned, mesh)
    toks, t_prefill, t_gen = generate(cm, pruned, prompts, GEN, max_len,
                                      mesh=mesh)
    toks = np.asarray(toks)
    print(f"  generate over the mesh: prefill {t_prefill:.2f}s, decode "
          f"{t_gen:.2f}s (first calls, compile included); tokens equal to "
          f"one device's: {float((toks == toks_one).mean())!r}", flush=True)
    if toks.shape != (BATCH, GEN) or toks.min() < 0 or toks.max() >= cfg.vocab:
        fail(f"mesh generate returned {toks.shape} tokens in "
             f"[{toks.min()}, {toks.max()}]")
    many = last_logits(cm, pruned, prompts, max_len, mesh=mesh)
    return compare_logits("4-chip mesh vs one device prefill", many, one)


def check_kernels() -> None:
    """Every kernel wrapper the run built went through Mosaic, and the
    bitmap kernel was among them."""
    from repro.kernels import ops as kops
    keys = kops.wrapper_keys()
    interpreted = [k for k in keys if k[-1] is True]
    if interpreted:
        fail(f"kernels built in interpret mode: {interpreted}")
    if not any(k[0] == "bitmap" for k in keys):
        fail(f"no bitmap kernel was compiled (wrappers: {keys})")
    print(f"kernels: {len(keys)} Mosaic-compiled wrappers "
          f"({', '.join(sorted({k[0] for k in keys}))}), none interpreted",
          flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip mesh phase")
    args = ap.parse_args(argv)

    device = device_info(args.chips)
    import jax
    from repro.launch.compile_cache import configure_compile_cache
    print(f"compile cache: {configure_compile_cache()}", flush=True)
    clock = CompileClock()

    cfg = smoke_config()
    print_reckoning(cfg)
    if args.chips == 4:
        mesh_phase(cfg)
    else:
        cm, pruned = build(cfg)
        static_phase(cm, pruned)
        mixer_phase(cm, pruned)
        check_no_demotion(cm)
    check_kernels()
    stats = jax.devices()[0].memory_stats() or {}
    print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')} "
          f"(device 0 of {device['count']})", flush=True)
    print(f"compile: {clock.compiles} backend compiles, "
          f"{clock.seconds:.1f}s; persistent-cache hits {clock.cache_hits}",
          flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
