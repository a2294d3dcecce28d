"""Serving-plane benchmark: batched prefill + KV-cache decode throughput,
compressed (scan-compiled Pallas kernels) vs dense, per batch size.

Rows (us_per_call = warm wall-clock of the phase):

  * ``serve_prefill_{dense|comp}_b{B}`` — one batched prefill pass
    (``Model.prefill`` / ``CompressedModel.prefill``, jitted, warm);
    derived: tokens/sec and tokens/sec/device.
  * ``serve_decode_{dense|comp}_b{B}``  — one greedy decode step against
    the prefill-filled cache (jitted, warm); derived: tokens/sec(/device).
    Compressed rows also surface the plan's :class:`FallbackReason` counts
    and the kernel jit-cache stats (hits/misses/entries) — the whole
    serving trace should cost one kernel build per planned role, NOT
    ``n_layers ×`` that.
  * ``serve_pipeline_vs_naive``         — the scanned compressed forward
    with the double-buffered streaming kernels (the dispatch default)
    against the same forward forced onto the naive grid-walk kernels
    (``repro.kernels.ops.pipeline_default``), warm and trace-time, with
    the numerical diff (parity-pinned ≈ 0).
  * ``serve_scan_vs_unrolled``          — the tentpole comparison: the
    scanned compressed forward (one compiled block, HLO O(1) in depth)
    vs the previous revision's per-layer Python re-drive, first-call
    (trace + compile) and warm.
  * ``serve_guarded_vs_unguarded``      — the robustness-layer overhead:
    the guarded driver (store verification, per-step finite-logit check,
    undonated decode cache — :func:`repro.runtime.guard.guarded_generate`)
    vs the plain driver on the same healthy store, whole-generation
    decode seconds per token, plus the health summary and a token-
    equality check (guarded must change nothing when nothing is wrong).
  * ``serve_mixer_vs_static``           — continuous batching: a
    mixed-length request stream through the compressed plane's
    :class:`repro.launch.mixer.Mixer` (admit/evict into a running decode
    batch) vs the same requests as static lockstep chunks (left-padded,
    each chunk decoding to its longest budget).  Useful-token decode
    throughput for both, the ratio, and the structural win: the mixer
    refills freed slots instead of burning lockstep steps past short
    requests' budgets.

Dense rows serve the SAME pruned weight tree the compressed store was
built from, so the comparison isolates the execution path.  With more
than one device, the request batch shards over a ``make_serve_mesh`` data
axis and throughput is reported per device.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import time

import jax
import numpy as np

from benchmarks.common import emit


def _serve_times(model, params, prompts, gen: int, max_len: int):
    """(warm prefill seconds, warm per-decode-step seconds)."""
    import jax.numpy as jnp

    from repro.exec.dispatch import jit_serving

    b, plen = prompts.shape
    prefill = jit_serving(model,
                          functools.partial(model.prefill, max_len=max_len))
    step = jit_serving(model, model.decode_step, donate_argnums=(1,))

    logits, cache = prefill(params, prompts)        # warm (trace/compile)
    jax.block_until_ready(logits)
    t0 = time.perf_counter()
    logits, cache = prefill(params, prompts)
    jax.block_until_ready(logits)
    t_prefill = time.perf_counter() - t0

    tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
    logits, cache = step(params, cache, tok,        # warm the decode step
                         jnp.asarray(plen, jnp.int32))
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    jax.block_until_ready(tok)
    t1 = time.perf_counter()
    for t in range(plen + 1, plen + 1 + gen):
        logits, cache = step(params, cache, tok, jnp.asarray(t, jnp.int32))
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    jax.block_until_ready(logits)
    t_step = (time.perf_counter() - t1) / gen
    return t_prefill, t_step


def _first_and_warm(fn, *args):
    """(first-call seconds — trace + compile —, warm-call seconds)."""
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    t_first = time.perf_counter() - t0
    t1 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    return t_first, time.perf_counter() - t1


def _rate(n: float, t: float) -> float:
    """n / t with a denominator floor — a quick run can time a warm phase
    at ~0s, which must not blow up the report."""
    return n / max(t, 1e-9)


def run(quick: bool = False) -> None:
    import jax.numpy as jnp

    from repro import exec as rexec
    from repro.configs import get_config
    from repro.core.cosearch import CoSearchConfig
    from repro.core.engine import EngineConfig
    from repro.core.sparsity import BlockBernoulli
    from repro.kernels import ops as kops
    from repro.launch.mesh import (axis_map_for, make_serve_mesh,
                                   mesh_axis_sizes)
    from repro.models.sharding import logical_axis_rules
    from repro.models.transformer import Model

    cfg = get_config("chatglm3-6b").reduced()
    if not quick:
        # deepen the stack so the scan-vs-unrolled trace gap is visible
        cfg = dataclasses.replace(cfg, n_layers=8)
    batches = (1, 2) if quick else (1, 8, 64)
    plen, gen = (8, 4) if quick else (32, 16)
    fast = CoSearchConfig(objective="edp",
                          engine=EngineConfig(max_levels=2,
                                              max_allocs_per_pattern=16),
                          spatial_top=2, max_pairs=6)

    model = Model(cfg)
    params = model.init(jax.random.key(0))
    plan = rexec.build_exec_plan(cfg, BlockBernoulli(0.5, 32 * 32),
                                 tokens=plen * max(batches),
                                 search_cfg=fast, value_bits=32)
    pruned = rexec.prune_params(params, plan, cfg)
    store = rexec.compress_params(pruned, plan, cfg)
    cm = rexec.CompressedModel(model, store)
    fb = plan.fallback_counts()
    rng = np.random.default_rng(0)

    kops.clear_kernel_cache()
    for b in batches:
        prompts = jnp.asarray(rng.integers(0, cfg.vocab, (b, plen)),
                              jnp.int32)
        mesh = make_serve_mesh(b)
        ndev = int(np.prod(list(mesh_axis_sizes(mesh).values()))) \
            if mesh is not None else 1
        ctx = contextlib.nullcontext() if mesh is None else mesh
        rules = contextlib.nullcontext() if mesh is None \
            else logical_axis_rules(axis_map_for(mesh), mesh=mesh)
        with ctx, rules:
            for label, m in (("dense", model), ("comp", cm)):
                t_prefill, t_step = _serve_times(m, pruned, prompts, gen,
                                                 plen + gen + 1)
                extra = ""
                if label == "comp":
                    kc = kops.kernel_cache_stats()
                    extra = (f" ratio={store.achieved_ratio():.3f}"
                             f" fallbacks={fb or 'none'}"
                             f" kcache=h{kc['hits']}/m{kc['misses']}"
                             f"/e{kc['entries']}")
                emit(f"serve_prefill_{label}_b{b}", t_prefill * 1e6,
                     f"tok/s={_rate(b * plen, t_prefill):.0f} "
                     f"tok/s/dev={_rate(b * plen, t_prefill) / ndev:.0f} "
                     f"plen={plen} ndev={ndev}{extra}")
                emit(f"serve_decode_{label}_b{b}", t_step * 1e6,
                     f"tok/s={_rate(b, t_step):.0f} "
                     f"tok/s/dev={_rate(b, t_step) / ndev:.0f} "
                     f"gen={gen} ndev={ndev}{extra}")

    # memory-pipeline row: the SAME scanned compressed forward with the
    # double-buffered streaming kernels (the default) vs the naive
    # grid-walk kernels, both jitted and warm — results are numerically
    # identical (the kernels are parity-pinned), so the ratio is what the
    # weight-streaming pipeline buys the serving plane end to end
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, (2, plen)), jnp.int32)
    with kops.pipeline_default(True):
        pipe_first, pipe_warm = _first_and_warm(
            jax.jit(cm.hidden_states), pruned, tokens)
        y_pipe = jax.jit(cm.hidden_states)(pruned, tokens)
    with kops.pipeline_default(False):
        naive_first, naive_warm = _first_and_warm(
            jax.jit(cm.hidden_states), pruned, tokens)
        y_naive = jax.jit(cm.hidden_states)(pruned, tokens)
    maxdiff = float(jnp.max(jnp.abs(y_pipe - y_naive)))
    emit("serve_pipeline_vs_naive", pipe_warm * 1e6,
         f"naive/pipelined warm={naive_warm / max(pipe_warm, 1e-9):.2f}x "
         f"trace={naive_first / max(pipe_first, 1e-9):.2f}x "
         f"maxdiff={maxdiff:.1e}")

    # tentpole row: scanned compressed forward vs per-layer unrolled
    scan_first, scan_warm = _first_and_warm(
        jax.jit(cm.hidden_states), pruned, tokens)
    unr_first, unr_warm = _first_and_warm(
        jax.jit(cm.hidden_states_unrolled), pruned, tokens)
    emit("serve_scan_vs_unrolled", scan_warm * 1e6,
         f"scan_trace_ms={scan_first * 1e3:.0f} "
         f"unrolled_trace_ms={unr_first * 1e3:.0f} "
         f"unrolled_warm_us={unr_warm * 1e6:.0f} layers={cfg.n_layers} "
         f"speedup_trace={_rate(unr_first, scan_first):.2f}x "
         f"speedup_warm={_rate(unr_warm, scan_warm):.2f}x")

    # robustness row: the guarded serving path vs the plain driver on the
    # same healthy store.  Both drivers re-jit their decode step per
    # invocation, so each side's decode time includes one compile plus the
    # per-step work — the delta is the guard's real cost (finite-logit
    # host sync each step + the undonated cache copy)
    from repro.launch import serve as serve_mod
    from repro.runtime.guard import guarded_generate
    prompts = jnp.asarray(rng.integers(0, cfg.vocab, (2, plen)), jnp.int32)
    toks_u, _, t_gen_u = serve_mod.generate(cm, pruned, prompts, gen,
                                            plen + gen)
    toks_g, report = guarded_generate(cm, pruned, prompts, gen, plen + gen)
    step_u = t_gen_u / gen
    step_g = report.t_decode_s / max(report.steps, 1)
    emit("serve_guarded_vs_unguarded", step_g * 1e6,
         f"unguarded_us={step_u * 1e6:.0f} "
         f"overhead={step_g / max(step_u, 1e-9):.2f}x gen={gen} "
         f"healthy={report.healthy} verify_roles={len(report.verify)} "
         f"retries={report.retries} "
         f"fallbacks={report.fallback_counts() or 'none'} "
         f"tokens_match={bool(jnp.all(toks_u == toks_g))}")

    # observability row: the SAME static generate with telemetry off vs
    # with a tracer + metrics registry installed (repro.obs).  The off
    # path must stay bit-identical — the span/event helpers reduce to one
    # None check — and the on path's ratio is the plane's real cost; both
    # sides are warm (the guarded row above already traced this shape)
    from repro.obs import metrics as omet
    from repro.obs import trace as otr
    toks_off, _, t_off = serve_mod.generate(cm, pruned, prompts, gen,
                                            plen + gen)
    tracer = otr.Tracer()
    reg = omet.MetricsRegistry()
    with otr.tracing(tracer), omet.collecting(reg):
        toks_on, _, t_on = serve_mod.generate(cm, pruned, prompts, gen,
                                              plen + gen)
    snap = reg.snapshot()
    emit("serve_telemetry_overhead", t_on / gen * 1e6,
         f"off_us={t_off / gen * 1e6:.0f} "
         f"overhead={t_on / max(t_off, 1e-9):.2f}x "
         f"trace_events={len(tracer.events)} "
         f"counter_series={len(snap['counters'])} "
         f"tokens_match={bool(jnp.all(toks_off == toks_on))}")

    # continuous-batching row: a mixed-length request stream through the
    # mixer vs the SAME requests served as static lockstep chunks.
    # Budgets alternate short/long so lockstep burns steps past the short
    # requests; the mixer refills those slots instead.  The mixer's decode
    # trace is warmed by a throwaway stream (its jitted step is per-Mixer);
    # the static driver re-jits per generate() call, the same caveat as
    # the guarded row above.
    from repro.launch.mixer import Mixer, Request
    slots = 2 if quick else 4
    n_req = 4 if quick else 8
    budgets = [gen if i % 2 else max(2, gen // 4) for i in range(n_req)]
    plens = [max(1, plen - (i % 4) * (plen // 5)) for i in range(n_req)]
    max_len = plen + gen + 1
    PAD = 0  # prompt pad id: prompts below draw from [1, vocab)

    def stream(tag):
        return [Request(uid=f"{tag}{i}",
                        prompt=jnp.asarray(rng.integers(
                            1, cfg.vocab, (plens[i],)), jnp.int32),
                        max_new=budgets[i])
                for i in range(n_req)]

    mx = Mixer(cm, pruned, slots=slots, max_len=max_len)
    mx.run(stream("warm"))                       # warm decode/prefill traces
    s0 = mx.stats()
    reqs = stream("req")
    mx.run(reqs)
    s1 = mx.stats()
    mix_tok = s1["tokens"] - s0["tokens"]
    mix_t = s1["t_decode_s"] - s0["t_decode_s"]
    mix_steps = s1["steps"] - s0["steps"]

    stat_tok, stat_t, stat_steps = 0, 0.0, 0
    for c0 in range(0, n_req, slots):
        idx = list(range(c0, min(c0 + slots, n_req)))
        cp = max(plens[i] for i in idx)
        cg = max(budgets[i] for i in idx)
        rows = [np.concatenate([np.full(cp - plens[i], PAD, np.int32),
                                np.asarray(reqs[i].prompt)]) for i in idx]
        batch = jnp.asarray(np.stack(rows))
        _, _, t_g = serve_mod.generate(cm, pruned, batch, cg, max_len,
                                       prompt_pad_id=PAD)
        stat_t += t_g
        stat_tok += sum(budgets[i] for i in idx)   # useful tokens only
        stat_steps += cg
    mix_rate = _rate(mix_tok, mix_t)
    stat_rate = _rate(stat_tok, stat_t)
    emit("serve_mixer_vs_static", mix_t / max(mix_tok, 1) * 1e6,
         f"mixer_tok_s={mix_rate:.0f} static_tok_s={stat_rate:.0f} "
         f"mixer/static={_rate(mix_rate, stat_rate):.2f}x "
         f"tok/s/dev={mix_rate / ndev:.0f} "
         f"slots={slots} requests={n_req} "
         f"mixer_steps={mix_steps} static_steps={stat_steps} "
         f"slot_reuse_admits={s1['slot_reuse_admits'] - s0['slot_reuse_admits']}")


if __name__ == "__main__":
    print("name,us_per_call,derived")
    run()
