"""Batched serving driver: one-pass prefill + KV-cache greedy decode, for
the dense :class:`~repro.models.transformer.Model` AND the execution
plane's :class:`~repro.exec.dispatch.CompressedModel` (same surface), with
per-phase tokens/sec(/device) reporting and optional mesh sharding.

:func:`generate` prefers the batched ``prefill`` path (one compiled
full-sequence forward fills the whole cache); families without it — ring
windows, hybrid/SSM/encdec states — keep the exact token-by-token decode
ingest.  LEFT-padded ragged prompts are supported via ``prompt_pad_id``
(each row is prefilled alone at its real length and decoded with a
per-row position vector — the mixer's admission primitive); ``eos_id``
stops decode early once every row has emitted EOS, padding the tail with
``pad_id``.  With a mesh (``make_serve_mesh``), the request batch shards
over the data axis and the model zoo's logical-axis annotations bind to
it.  For continuous batching over a request STREAM (admit/evict into a
running decode batch, sampled decoding) see :mod:`repro.launch.mixer` and
the ``--mixer`` CLI mode.

CPU quickstart (reduced config):
  PYTHONPATH=src python -m repro.launch.serve --arch chatglm3-6b --reduced \
      --batch 4 --prompt-len 32 --gen 16 [--compressed] [--mesh] \
      [--mixer --slots 2 --temperature 0.8 --top-k 20 --eos 7]
"""

from __future__ import annotations

import argparse
import contextlib
import functools
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.exec.dispatch import jit_serving
from repro.launch.compile_cache import configure_compile_cache
from repro.launch.mesh import axis_map_for, make_serve_mesh, mesh_axis_sizes
from repro.models.sharding import logical_axis_rules, named_sharding
from repro.models.transformer import Model
from repro.obs import metrics as omet
from repro.obs import trace as otr


def _rate(n: float, t: float) -> float:
    """tokens / seconds with a floor on the denominator: a tiny
    ``--reduced --gen 1`` run can legitimately time ~0s, which must not
    turn the report into a ZeroDivisionError (or an inf row)."""
    return n / max(t, 1e-9)


def _prompt_offsets(prompts: jax.Array, prompt_pad_id: Optional[int]
                    ) -> np.ndarray:
    """Per-row first-real-token offsets of a LEFT-padded prompt batch.

    With ``prompt_pad_id`` None every prompt is taken as unpadded (offset
    0).  Otherwise each row must be ``[pad... real...]`` with at least one
    real token — pads after the first real token (right/interior padding)
    are rejected loudly instead of silently mis-positioning the row."""
    b, plen = prompts.shape
    if prompt_pad_id is None:
        return np.zeros(b, np.int64)
    pn = np.asarray(prompts)
    real = pn != prompt_pad_id
    offsets = np.argmax(real, axis=1)
    for r in range(b):
        if not real[r].any():
            raise ValueError(f"prompt row {r} is all padding "
                             f"(pad_id={prompt_pad_id})")
        if not real[r, offsets[r]:].all():
            raise ValueError(
                f"prompt row {r} has pad tokens after its first real "
                f"token; prompts must be LEFT-padded (pad_id="
                f"{prompt_pad_id})")
    return offsets


def _generate(model, params, prompts: jax.Array, gen: int, max_len: int,
              eos_id: Optional[int] = None, pad_id: int = -1,
              prompt_pad_id: Optional[int] = None):
    b, plen = prompts.shape
    if plen > max_len or plen + gen > max_len:
        raise ValueError(f"prompt ({plen}) + gen ({gen}) exceeds "
                         f"max_len ({max_len})")
    offsets = _prompt_offsets(prompts, prompt_pad_id)
    step = jit_serving(model, model.decode_step, donate_argnums=(1,))
    tid = otr.trace_id()

    t0 = time.perf_counter()
    with otr.span("prefill", trace_id=tid, batch=b, plen=plen,
                  ragged=bool(offsets.any())):
        if offsets.any():
            # ragged left-padded rows: admit each row alone at its REAL
            # length (batch-1 prefill or exact token ingest) into its slot
            # of the shared cache, then decode with a per-row position
            # vector — the continuous-batching admission primitive
            # (launch.mixer)
            from repro.launch import mixer as mixer_mod
            cache = model.init_cache(b, max_len)
            write = jax.jit(mixer_mod.write_slot, donate_argnums=(0,))
            lasts = []
            for r in range(b):
                with otr.span("admit", trace_id=tid, row=r,
                              prompt_len=plen - int(offsets[r])):
                    last, rcache = mixer_mod.prefill_request(
                        model, params, prompts[r:r + 1, int(offsets[r]):],
                        max_len)
                    cache = write(cache, rcache, jnp.asarray(r, jnp.int32))
                lasts.append(last)
            logits = jnp.stack(lasts)
            pos = jnp.asarray(plen - offsets, jnp.int32)   # per-row (B,)
            jax.block_until_ready(logits)
        else:
            pos = None                                     # lockstep scalar
            try:
                prefill = jit_serving(
                    model, functools.partial(model.prefill, max_len=max_len))
                all_logits, cache = prefill(params, prompts)
                logits = all_logits[:, -1]
                jax.block_until_ready(logits)
            except NotImplementedError:
                # ring windows / hybrid / ssm / encdec: exact decode-path
                # ingest
                cache = model.init_cache(b, max_len)
                logits = None
                for t in range(plen):
                    logits, cache = step(params, cache, prompts[:, t],
                                         jnp.asarray(t, jnp.int32))
                jax.block_until_ready(logits)
    t_prefill = time.perf_counter() - t0

    out = []
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    done = np.zeros(b, bool)              # rows that already emitted EOS
    t1 = time.perf_counter()
    with otr.span("decode", trace_id=tid, batch=b, gen=gen):
        for i, t in enumerate(range(plen, plen + gen)):
            if eos_id is None:
                out.append(tok)
            else:
                # a row's EOS token is emitted; everything after it holds
                # pad_id, and once EVERY row is done the remaining steps
                # are skipped instead of decoded and thrown away
                out.append(jnp.where(jnp.asarray(done), pad_id, tok))
                done |= np.asarray(tok) == eos_id
                if done.all():
                    break
            cur = jnp.asarray(t, jnp.int32) if pos is None else pos + i
            logits, cache = step(params, cache, tok, cur)
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        jax.block_until_ready(out[-1] if out else logits)
    t_gen = time.perf_counter() - t1
    omet.counter_inc("serve_static_tokens_total", b * len(out))
    if len(out) < gen:
        pad = jnp.full((b,), pad_id, jnp.int32)
        out.extend([pad] * (gen - len(out)))
    return jnp.stack(out, axis=1), t_prefill, t_gen


def generate(model, params, prompts: jax.Array, gen: int, max_len: int,
             mesh=None, guarded: bool = False,
             eos_id: Optional[int] = None, pad_id: int = -1,
             prompt_pad_id: Optional[int] = None, **guard_kwargs):
    """Greedy decode for a batch of prompts.

    ``model`` is anything with the serving surface (``prefill`` /
    ``init_cache`` / ``decode_step``): the dense Model or a
    CompressedModel.  Returns (tokens (B, gen), t_prefill_s, t_gen_s).
    Prompts are equal-length by default; pass ``prompt_pad_id`` to serve
    LEFT-padded ragged rows (each row prefills alone at its real length
    and decodes at its own position).  ``eos_id`` ends rows early — the
    EOS token is emitted, later positions hold ``pad_id``, and decode
    stops once every row is done.  With ``mesh``, requests shard over the
    data axis and the models' logical-axis annotations bind for the whole
    prefill+decode scope.

    ``guarded=True`` routes through the robustness layer
    (:func:`repro.runtime.guard.guarded_generate`: store verification,
    per-role dense demotion, NaN/Inf retry, deadline) and appends the
    :class:`~repro.runtime.guard.HealthReport` to the return tuple;
    ``guard_kwargs`` (``verify=``, ``deadline_s=``, ``max_retries=``,
    ``dense_model=``) pass through."""
    if guarded:
        from repro.runtime.guard import guarded_generate
        if prompt_pad_id is not None:
            raise NotImplementedError(
                "guarded serving takes equal-length prompts; serve ragged "
                "streams through repro.launch.mixer")
        toks, report = guarded_generate(model, params, prompts, gen, max_len,
                                        mesh=mesh, eos_id=eos_id,
                                        pad_id=pad_id, **guard_kwargs)
        return toks, report.t_prefill_s, report.t_decode_s, report
    if mesh is None:
        return _generate(model, params, prompts, gen, max_len,
                         eos_id=eos_id, pad_id=pad_id,
                         prompt_pad_id=prompt_pad_id)
    with mesh, logical_axis_rules(axis_map_for(mesh), mesh=mesh):
        prompts = jax.device_put(prompts,
                                 named_sharding(mesh, "batch", None))
        return _generate(model, params, prompts, gen, max_len,
                         eos_id=eos_id, pad_id=pad_id,
                         prompt_pad_id=prompt_pad_id)


def _fast_plan(cfg, tokens: int):
    """A small-budget co-searched plan for CLI/demo serving."""
    from repro.core.cosearch import CoSearchConfig
    from repro.core.engine import EngineConfig
    from repro.core.sparsity import BlockBernoulli
    from repro.exec import build_exec_plan
    scfg = CoSearchConfig(objective="edp",
                          engine=EngineConfig(max_levels=2,
                                              max_allocs_per_pattern=16),
                          spatial_top=2, max_pairs=6)
    return build_exec_plan(cfg, BlockBernoulli(0.5, 32 * 32),
                           tokens=tokens, search_cfg=scfg, value_bits=32)


def compressed_model(cfg, params, tokens: int = 64):
    """Plan → prune → compress → :class:`CompressedModel` in one call
    (shared by the CLI and the serving examples).  Returns
    (compressed_model, pruned_params) — serve with the PRUNED tree."""
    from repro.exec import (CompressedModel, compress_params, prune_params)
    model = Model(cfg)
    plan = _fast_plan(cfg, tokens)
    pruned = prune_params(params, plan, cfg)
    store = compress_params(pruned, plan, cfg)
    return CompressedModel(model, store), pruned


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--compressed", action="store_true",
                    help="co-search a plan and serve the compressed store")
    ap.add_argument("--mesh", action="store_true",
                    help="shard the request batch over available devices")
    ap.add_argument("--guarded", action="store_true",
                    help="serve through the robustness layer (verify + "
                         "retry + dense degradation) and print the health "
                         "report")
    ap.add_argument("--deadline", type=float, default=None,
                    help="per-request wall-clock budget in seconds "
                         "(guarded / mixer modes)")
    ap.add_argument("--mixer", action="store_true",
                    help="continuous batching: serve a mixed-length request "
                         "stream through repro.launch.mixer instead of one "
                         "static lockstep batch")
    ap.add_argument("--slots", type=int, default=None,
                    help="decode slots for --mixer (default: --batch)")
    ap.add_argument("--eos", type=int, default=None,
                    help="EOS token id: rows/requests stop early once it is "
                         "emitted (tail padded with pad_id)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature for --mixer requests "
                         "(0 = greedy)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="top-k cutoff for sampled --mixer decoding "
                         "(0 = full vocab)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="capture a span trace of the run and write Chrome "
                         "trace-event JSON (load in chrome://tracing) plus "
                         "PATH.stable.json, the deterministic projection")
    ap.add_argument("--metrics", default=None, metavar="PATH",
                    help="collect serving metrics and write a JSON snapshot "
                         "to PATH plus Prometheus text exposition to "
                         "PATH.prom")
    args = ap.parse_args()
    configure_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    label = cfg.name
    ratio = None
    if args.compressed:
        model, params = compressed_model(cfg, params)
        ratio = model.store.achieved_ratio()
        fb = model.store.plan.fallback_counts()
        label += f" [compressed: ratio={ratio:.3f} fallbacks={fb or 'none'}]"
    mesh = make_serve_mesh(args.batch) if args.mesh else None
    ndev = int(np.prod(list(mesh_axis_sizes(mesh).values()))) if mesh else 1

    # telemetry (--trace / --metrics): contexts wrap the serving run only —
    # model build and planning stay outside so the exports tell the
    # REQUESTS' story
    tel = contextlib.ExitStack()
    tracer = None
    registry = None
    exec_counters = None
    if args.trace is not None:
        tracer = otr.Tracer()
        tel.enter_context(otr.tracing(tracer))
    if args.metrics is not None:
        registry = omet.MetricsRegistry()
        tel.enter_context(omet.collecting(registry))
    if tracer is not None or registry is not None:
        from repro.obs.profile import kernel_timer
        tel.enter_context(kernel_timer(registry=registry, tracer=tracer))
        if args.compressed:
            from repro.exec import dispatch as exec_dispatch
            exec_counters = tel.enter_context(exec_dispatch.instrument())

    def _telemetry_done(mx=None) -> None:
        """Close the capture contexts, fold the passive sources in, export."""
        tel.close()
        if registry is not None:
            if exec_counters is not None:
                omet.ingest_instrument(registry, exec_counters)
            omet.collect_caches(registry)
            if mx is not None:
                omet.ingest_straggler(registry, mx.straggler)
            if ratio is not None:
                registry.gauge_set("serve_achieved_compression_ratio", ratio)
            registry.save(args.metrics)
            with open(args.metrics + ".prom", "w") as fh:
                fh.write(registry.prometheus_text())
            print(f"  metrics: {args.metrics} (+ {args.metrics}.prom)")
        if tracer is not None:
            tracer.save_chrome(args.trace)
            tracer.save_stable(args.trace + ".stable.json")
            print(f"  trace: {args.trace} ({len(tracer.events)} events; "
                  f"stable projection at {args.trace}.stable.json)")

    rng = np.random.default_rng(0)

    if args.mixer:
        from repro.launch.mixer import Mixer, Request
        slots = args.slots or args.batch
        max_len = args.prompt_len + args.gen
        # mixed-length stream: prompt lengths cycle below --prompt-len so
        # admissions land at distinct positions (the point of the mixer)
        reqs = []
        for i in range(args.batch):
            plen = max(1, args.prompt_len - (i % 4) * (args.prompt_len // 5))
            reqs.append(Request(
                uid=f"req{i}",
                prompt=jnp.asarray(
                    rng.integers(0, cfg.vocab, (plen,)), jnp.int32),
                max_new=args.gen, temperature=args.temperature,
                top_k=args.top_k, seed=i))
        mx = Mixer(model, params, slots=slots, max_len=max_len,
                   eos_id=args.eos, deadline_s=args.deadline)
        results = mx.run(reqs)
        st = mx.stats()
        print(f"[serve/mixer] {label}: slots={slots} devices={ndev} "
              f"requests={len(reqs)}")
        plens = {r.uid: len(r.prompt) for r in reqs}
        for res in results:
            print(f"  {res.uid}: prompt={plens[res.uid]} "
                  f"tok={res.n_tokens}/{len(res.tokens)} slot={res.slot} "
                  f"admit_step={res.admit_step} "
                  f"eos={res.report.eos_hit} out={res.tokens[:6]}")
        print(f"  decode  {st['tokens']} tok in {st['t_decode_s']:.2f}s "
              f"over {st['steps']} steps "
              f"({_rate(st['tokens'], st['t_decode_s']):.1f} tok/s, "
              f"{_rate(st['tokens'], st['t_decode_s']) / ndev:.1f} "
              f"tok/s/dev) slot_reuse_admits={st['slot_reuse_admits']}")
        _telemetry_done(mx)
        return

    prompts = jnp.asarray(
        rng.integers(0, cfg.vocab, (args.batch, args.prompt_len)), jnp.int32)

    report = None
    if args.guarded:
        toks, t_prefill, t_gen, report = generate(
            model, params, prompts, args.gen, args.prompt_len + args.gen,
            mesh=mesh, guarded=True, deadline_s=args.deadline,
            eos_id=args.eos)
    else:
        toks, t_prefill, t_gen = generate(
            model, params, prompts, args.gen, args.prompt_len + args.gen,
            mesh=mesh, eos_id=args.eos)
    n_pref = args.batch * args.prompt_len
    n_gen = args.batch * args.gen
    print(f"[serve] {label}: batch={args.batch} devices={ndev}")
    print(f"  prefill {n_pref} tok in {t_prefill:.2f}s "
          f"({_rate(n_pref, t_prefill):.1f} tok/s, "
          f"{_rate(n_pref, t_prefill) / ndev:.1f} tok/s/dev)")
    print(f"  decode  {n_gen} tok in {t_gen:.2f}s "
          f"({_rate(n_gen, t_gen):.1f} tok/s, "
          f"{_rate(n_gen, t_gen) / ndev:.1f} tok/s/dev)")
    print(f"  sample out: {np.asarray(toks[0, :8])}")
    if report is not None:
        print(f"  health: healthy={report.healthy} "
              f"verify={report.verify or 'skipped'} "
              f"fallbacks={report.fallback_counts() or 'none'} "
              f"retries={report.retries} dense_steps={report.dense_steps} "
              f"deadline_hit={report.deadline_hit} "
              f"steps={report.steps}/{report.gen}")
    _telemetry_done()


if __name__ == "__main__":
    main()
