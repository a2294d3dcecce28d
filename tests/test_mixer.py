"""Continuous-batching mixer tests + static-path bugfix regressions.

Contracts pinned here:

  * a mixed-length request stream through :class:`repro.launch.mixer
    .Mixer` emits, per request, the SAME tokens as the request served
    alone through the static driver at fp32 — dense AND compressed
    (all-bitmap plan), with slots genuinely reused mid-stream (the
    acceptance gate: admission into a freed slot must not perturb any
    resident request);
  * seeded temperature/top-k sampling replays exactly across runs (keys
    are a pure function of request seed + token index, independent of
    slot placement);
  * eviction leaves stale KV in the slot and isolation still holds (the
    per-slot length mask, not cache clearing, is the mechanism);
  * ``serve.generate`` accepts LEFT-padded ragged prompts via
    ``prompt_pad_id`` (per-row first-real-token offsets) and rejects
    right/interior padding loudly — the pre-fix driver silently decoded
    pad tokens as context;
  * ``eos_id=`` ends decode early in both the static and guarded drivers:
    EOS is emitted, the tail holds ``pad_id``, and decode_step stops
    running once every row is done (counted via an effectful callback —
    the pre-fix drivers burned the full ``gen`` budget);
  * throughput reports survive ~0-second phases (``_rate`` denominator
    floor) — the pre-fix CLI divided by raw wall-clock;
  * an all-equal position VECTOR decodes bit-identically to the scalar
    position (the mixer's decode primitive degenerates to lockstep);
  * under a ``jax.profiler`` capture the mixer's host phases are spans on
    the profile's host plane: ``decode_step.{inputs,dispatch,readback,
    emit}`` in order inside ``decode_step``, ``admit.first_token`` inside
    ``admit`` — and the same phases, in the same order, in a tracer.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import exec as rexec
from repro.configs import get_config
from repro.core.cosearch import CoSearchConfig
from repro.core.engine import EngineConfig
from repro.core.sparsity import BlockBernoulli
from repro.launch import serve
from repro.launch.mixer import Mixer, Request, sample_token
from repro.models import attention as attn_mod
from repro.models import layers as L
from repro.models.transformer import Model
from repro.runtime.guard import guarded_generate

FAST = CoSearchConfig(objective="edp",
                      engine=EngineConfig(max_levels=2,
                                          max_allocs_per_pattern=16),
                      spatial_top=2, max_pairs=6)


@pytest.fixture()
def fp32_compute(monkeypatch):
    monkeypatch.setattr(L, "COMPUTE_DTYPE", jnp.float32)
    monkeypatch.setattr(attn_mod, "COMPUTE_DTYPE", jnp.float32)


def _cfg():
    return get_config("chatglm3-6b").reduced()


def _dense(seed=0):
    cfg = _cfg()
    model = Model(cfg)
    return cfg, model, model.init(jax.random.key(seed))


def _stream(cfg, plens, max_new, seed=0, **kw):
    rng = np.random.default_rng(seed)
    return [Request(uid=f"r{i}",
                    prompt=jnp.asarray(
                        rng.integers(1, cfg.vocab, (p,)), jnp.int32),
                    max_new=max_new[i] if isinstance(max_new, list)
                    else max_new, **kw)
            for i, p in enumerate(plens)]


def _assert_stream_matches_standalone(model, params, reqs, mx, max_len):
    results = mx.run(reqs)
    for req, res in zip(reqs, results):
        ref, _, _ = serve.generate(model, params,
                                   jnp.asarray(req.prompt)[None, :],
                                   req.max_new, max_len)
        np.testing.assert_array_equal(
            np.asarray(ref[0]), res.tokens,
            err_msg=f"{req.uid} (slot {res.slot}, admit_step "
                    f"{res.admit_step}) diverged from standalone")
    # the stream must actually exercise continuous batching: at least one
    # request admitted into a slot freed mid-decode
    reuse = [e for e in mx.events if e["event"] == "admit" and e["step"] > 0]
    assert reuse, f"no admit-into-freed-slot event: {mx.events}"
    return results


# ---------------------------------------------------------------------------
# mixer vs standalone (the acceptance gate)
# ---------------------------------------------------------------------------

def test_mixer_dense_stream_matches_standalone(fp32_compute):
    cfg, model, params = _dense()
    max_len = 48
    reqs = _stream(cfg, [3, 5, 7, 9, 11, 4, 6, 13],
                   [6, 7, 8, 6, 7, 8, 6, 7])
    mx = Mixer(model, params, slots=3, max_len=max_len)
    _assert_stream_matches_standalone(model, params, reqs, mx, max_len)
    st = mx.stats()
    assert st["admits"] == st["evictions"] == len(reqs)
    assert st["slot_reuse_admits"] >= 1
    assert st["tokens"] == sum(r.max_new for r in reqs)


def test_mixer_compressed_bitmap_stream_matches_standalone(fp32_compute):
    cfg, model, params = _dense()
    plan = rexec.build_exec_plan(cfg, BlockBernoulli(0.5, 32 * 32),
                                 tokens=64, search_cfg=FAST, value_bits=32)
    pruned = rexec.prune_params(params, plan, cfg)
    store = rexec.compress_params(pruned, plan, cfg)
    cm = rexec.CompressedModel(model, store)
    max_len = 48
    reqs = _stream(cfg, [3, 5, 7, 9, 11, 4, 6, 13],
                   [6, 7, 8, 6, 7, 8, 6, 7], seed=1)
    results, mx = cm.serve_mixed(pruned, reqs, slots=3, max_len=max_len)
    for req, res in zip(reqs, results):
        ref, _, _ = serve.generate(cm, pruned,
                                   jnp.asarray(req.prompt)[None, :],
                                   req.max_new, max_len)
        np.testing.assert_array_equal(np.asarray(ref[0]), res.tokens)
    assert mx.stats()["slot_reuse_admits"] >= 1


def test_mixer_sampled_stream_replays_exactly(fp32_compute):
    cfg, model, params = _dense()
    max_len = 32

    def one_run():
        reqs = _stream(cfg, [3, 6, 4, 8], 5, temperature=0.8, top_k=16)
        reqs = [Request(uid=r.uid, prompt=r.prompt, max_new=r.max_new,
                        temperature=r.temperature, top_k=r.top_k, seed=i)
                for i, r in enumerate(reqs)]
        mx = Mixer(model, params, slots=2, max_len=max_len)
        return [res.tokens for res in mx.run(reqs)]

    a, b = one_run(), one_run()
    for ta, tb in zip(a, b):
        np.testing.assert_array_equal(ta, tb)
    # sampling is actually on: different seeds draw different tokens
    # somewhere in the stream (astronomically unlikely to all collide)
    assert any(not np.array_equal(a[i], a[j])
               for i in range(len(a)) for j in range(i + 1, len(a)))


def test_mixer_slot_reuse_isolation(fp32_compute):
    # a long predecessor fills its slot's KV deep; the successor admitted
    # into the SAME slot must decode as if the cache were fresh
    cfg, model, params = _dense()
    max_len = 40
    long_req, short_req = _stream(cfg, [20, 4], [3, 8], seed=2)
    mx = Mixer(model, params, slots=1, max_len=max_len)
    results = mx.run([long_req, short_req])
    assert results[0].slot == results[1].slot == 0
    assert results[1].admit_step > 0
    alone = Mixer(model, params, slots=1, max_len=max_len)
    ref = alone.run([short_req])[0]
    np.testing.assert_array_equal(results[1].tokens, ref.tokens)


def test_mixer_eos_and_validation(fp32_compute):
    cfg, model, params = _dense()
    max_len = 24
    reqs = _stream(cfg, [4, 4], 6, seed=3)
    # probe the greedy stream to find a token to use as EOS
    probe = Mixer(model, params, slots=2, max_len=max_len)
    toks0 = probe.run(reqs)[0].tokens
    eos = int(toks0[2])

    mx = Mixer(model, params, slots=2, max_len=max_len, eos_id=eos,
               pad_id=-7)
    res = mx.run(reqs)[0]
    stop = int(np.nonzero(toks0 == eos)[0][0])
    np.testing.assert_array_equal(res.tokens[:stop + 1], toks0[:stop + 1])
    assert (res.tokens[stop + 1:] == -7).all()
    assert res.report.eos_hit and res.n_tokens == stop + 1

    with pytest.raises(ValueError, match="unique"):
        Mixer(model, params, slots=2, max_len=max_len).run(
            [reqs[0], reqs[0]])
    with pytest.raises(ValueError, match="exceeds max_len"):
        Mixer(model, params, slots=1, max_len=6).admit(
            _stream(cfg, [5], 6)[0])
    with pytest.raises(ValueError, match="at least one slot"):
        Mixer(model, params, slots=0, max_len=max_len)


def test_mixer_deadline_evicts_with_report(fp32_compute):
    cfg, model, params = _dense()
    mx = Mixer(model, params, slots=1, max_len=24, deadline_s=0.0)
    res = mx.run(_stream(cfg, [4], 6, seed=4))[0]
    # prefill emits the first token; the first decode step hits the
    # zero-second budget and evicts with the guarded driver's semantics
    assert res.n_tokens == 1
    assert (res.tokens[1:] == -1).all()
    assert res.report.deadline_hit
    assert res.report.fallback_counts().get("deadline_exceeded") == 1


def test_sample_token_greedy_and_topk():
    logits = jnp.asarray([0.1, 3.0, 2.0, -1.0])
    greedy = Request(uid="g", prompt=[1], max_new=1)
    assert sample_token(logits, greedy, 0) == 1
    # top-1 sampling can only ever pick the argmax, any temperature
    top1 = Request(uid="t", prompt=[1], max_new=1, temperature=5.0,
                   top_k=1, seed=9)
    assert all(sample_token(logits, top1, i) == 1 for i in range(8))


# ---------------------------------------------------------------------------
# static-path regressions (the three driver bugfixes)
# ---------------------------------------------------------------------------

def test_serve_ragged_left_padded_matches_per_row(fp32_compute):
    cfg, model, params = _dense()
    PAD = 0
    rng = np.random.default_rng(5)
    rows = [rng.integers(1, cfg.vocab, (p,)).astype(np.int32)
            for p in (3, 7, 5)]
    plen = max(len(r) for r in rows)
    batch = jnp.asarray(np.stack(
        [np.concatenate([np.full(plen - len(r), PAD, np.int32), r])
         for r in rows]))
    out, _, _ = serve.generate(model, params, batch, 5, plen + 5,
                               prompt_pad_id=PAD)
    for r, row in enumerate(rows):
        ref, _, _ = serve.generate(model, params, jnp.asarray(row)[None, :],
                                   5, plen + 5)
        np.testing.assert_array_equal(np.asarray(ref[0]),
                                      np.asarray(out[r]))


def test_serve_rejects_right_or_interior_padding(fp32_compute):
    cfg, model, params = _dense()
    right = jnp.asarray([[5, 6, 7, 0, 0], [1, 2, 3, 4, 5]], jnp.int32)
    with pytest.raises(ValueError, match="LEFT-padded"):
        serve.generate(model, params, right, 2, 10, prompt_pad_id=0)
    interior = jnp.asarray([[0, 5, 0, 7, 8]], jnp.int32)
    with pytest.raises(ValueError, match="LEFT-padded"):
        serve.generate(model, params, interior, 2, 10, prompt_pad_id=0)
    allpad = jnp.asarray([[0, 0, 0]], jnp.int32)
    with pytest.raises(ValueError, match="all padding"):
        serve.generate(model, params, allpad, 2, 10, prompt_pad_id=0)


class _CountingModel:
    """Serving surface that counts EXECUTED decode steps (an effectful
    callback, so jit caching can't hide repeat invocations)."""

    def __init__(self, model):
        self._m = model
        self.cfg = model.cfg
        self.calls = 0

    def prefill(self, *a, **k):
        return self._m.prefill(*a, **k)

    def init_cache(self, *a, **k):
        return self._m.init_cache(*a, **k)

    def decode_step(self, params, cache, tokens, pos):
        jax.debug.callback(self._bump)
        return self._m.decode_step(params, cache, tokens, pos)

    def _bump(self):
        self.calls += 1


def test_serve_eos_early_exit(fp32_compute):
    cfg, model, params = _dense()
    rng = np.random.default_rng(6)
    pp = jnp.asarray(rng.integers(0, cfg.vocab, (1, 6)), jnp.int32)
    gen = 8
    cm = _CountingModel(model)

    full, _, _ = serve.generate(cm, params, pp, gen, 20)
    base = cm.calls
    assert base == gen
    eos = int(np.asarray(full)[0, 3])

    cm.calls = 0
    toks, _, _ = serve.generate(cm, params, pp, gen, 20, eos_id=eos,
                                pad_id=-7)
    tn = np.asarray(toks)[0]
    stop = int(np.nonzero(np.asarray(full)[0] == eos)[0][0])
    np.testing.assert_array_equal(tn[:stop + 1],
                                  np.asarray(full)[0, :stop + 1])
    assert (tn[stop + 1:] == -7).all()
    assert cm.calls == stop < base  # decode stopped at the EOS row


def test_guarded_eos_early_exit_matches_static(fp32_compute):
    cfg, model, params = _dense()
    rng = np.random.default_rng(6)
    pp = jnp.asarray(rng.integers(0, cfg.vocab, (1, 6)), jnp.int32)
    gen = 8
    full, _, _ = serve.generate(model, params, pp, gen, 20)
    eos = int(np.asarray(full)[0, 3])

    cm = _CountingModel(model)
    toks, rep = guarded_generate(cm, params, pp, gen, 20, verify=False,
                                 eos_id=eos, pad_id=-7)
    ref, _, _ = serve.generate(model, params, pp, gen, 20, eos_id=eos,
                               pad_id=-7)
    np.testing.assert_array_equal(np.asarray(toks), np.asarray(ref))
    assert rep.eos_hit and cm.calls < gen
    # eos_hit round-trips through the serialized report
    assert rep.to_dict()["eos_hit"] is True


def test_rate_guards_zero_durations():
    from benchmarks.bench_serve import _rate as bench_rate
    assert np.isfinite(serve._rate(100, 0.0))
    assert np.isfinite(bench_rate(100, 0.0))
    assert serve._rate(100, 2.0) == 50.0


# ---------------------------------------------------------------------------
# the decode primitive: vector positions
# ---------------------------------------------------------------------------

def test_vector_pos_degenerates_to_scalar(fp32_compute):
    cfg, model, params = _dense()
    toks = jnp.asarray(np.random.default_rng(7).integers(
        0, cfg.vocab, (2, 6)), jnp.int32)
    max_len = 12
    _, cache_a = model.prefill(params, toks, max_len)
    _, cache_b = model.prefill(params, toks, max_len)
    nxt = toks[:, -1]
    lg_s, c_s = model.decode_step(params, cache_a, nxt,
                                  jnp.asarray(6, jnp.int32))
    lg_v, c_v = model.decode_step(params, cache_b, nxt,
                                  jnp.asarray([6, 6], jnp.int32))
    np.testing.assert_array_equal(np.asarray(lg_s), np.asarray(lg_v))
    for a, b in zip(jax.tree.leaves(c_s), jax.tree.leaves(c_v)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_decode_step_rejects_bad_pos_shape(fp32_compute):
    cfg, model, params = _dense()
    cache = model.init_cache(2, 8)
    tok = jnp.asarray([1, 2], jnp.int32)
    with pytest.raises(ValueError, match="scalar or a per-slot vector"):
        model.decode_step(params, cache, tok, jnp.asarray([0, 0, 0],
                                                          jnp.int32))


# ---------------------------------------------------------------------------
# host phases on the profiler's clock
# ---------------------------------------------------------------------------

STEP_PHASES = ["decode_step.inputs", "decode_step.dispatch",
               "decode_step.readback", "decode_step.emit"]


def _nested(spans, parent, children):
    """Each ``parent`` span holds one of each child, in order, and no
    child lies outside a parent."""
    parents = [(a, b) for n, a, b in spans if n == parent]
    kids = sorted((a, b, n) for n, a, b in spans if n in children)
    assert parents and len(kids) == len(parents) * len(children)
    for a, b in parents:
        inside = [(ka, kb, n) for ka, kb, n in kids if a <= ka and kb <= b]
        assert [n for _, _, n in inside] == children
        ends = [a] + [x for ka, kb, _ in inside for x in (ka, kb)] + [b]
        assert ends == sorted(ends)


def test_traced_mixer_phases_nest_on_the_profile_host_plane(tmp_path):
    import glob
    from jax.profiler import ProfileData
    from repro.obs import trace as otr
    cfg, model, params = _dense()
    mx = Mixer(model, params, slots=2, max_len=16)
    reqs = _stream(cfg, [3, 4], 4)
    mx.admit(reqs[0])
    mx._step()                                  # compiled before the capture
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        mx.admit(reqs[1])
        for _ in range(2):
            mx._step()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    names = {"decode_step", "admit", "admit.first_token", *STEP_PHASES}
    spans = [(ev.name, ev.start_ns, ev.end_ns)
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events
             if ev.name in names]
    assert sum(n == "decode_step" for n, _, _ in spans) == 2
    _nested(spans, "decode_step", STEP_PHASES)
    _nested(spans, "admit", ["admit.first_token"])

    tracer = otr.Tracer()
    with otr.tracing(tracer):
        mx._step()
    order = [(e["ph"], e["name"]) for e in tracer.events if e["ph"] in "BE"]
    assert order == [("B", "decode_step")] + [
        (ph, n) for n in STEP_PHASES for ph in "BE"] + [("E", "decode_step")]
