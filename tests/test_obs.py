"""Observability-plane tests (PR 10 acceptance gates).

Contracts pinned here:

  * span begin/end nest strictly — a mismatched end RAISES instead of
    corrupting the stream — and with no tracer installed the module
    surface is a shared no-op (``span`` returns the same ``_NULL``
    object every call; ``trace_id`` is None);
  * :meth:`Tracer.chrome_trace` is valid Chrome trace-event JSON —
    ``B``/``E`` balanced, instants carry ``s``, every row
    JSON-serializable;
  * :meth:`Tracer.stable_trace` drops every timing field, keeps order
    and args, and excludes ``stable=False`` (timing-derived) events —
    two runs of the same seeded mixer stream (greedy AND sampled)
    produce byte-identical stable traces;
  * the metrics registry enforces its schema (a name is one type,
    counters only go up), histograms bucket with ``le`` semantics, the
    snapshot JSON round-trips, and the Prometheus text exposition
    parses with CUMULATIVE bucket series;
  * every ``ingest_*`` adapter reproduces its source of truth exactly
    (``instrument()`` OpCounters, HealthReport fields);
  * telemetry OFF leaves ``serve.generate`` results bit-identical, and
    telemetry ON does not change them either;
  * ``HealthReport``: ``stable_dict() | timings_dict() == to_dict()``,
    ``from_dict`` round-trips, ``trace_id`` links the report to its
    spans (``"t:<uid>"`` through the mixer, a tracer counter through
    the guarded driver) and stays None untraced;
  * a traced guarded run over a bit-flipped store emits the ``demote``
    event and the matching ``serve_verify_failures_total`` /
    ``serve_fallbacks_total`` counters;
  * while a ``jax.profiler`` session records, ``span`` is also a
    ``TraceAnnotation`` of the same name on the profile's host plane (and
    only then); the compiled serving steps carry the program's name scopes
    (``decode``, ``prefill``, ``slot_write``, ``attention``, ``kv_write``,
    ``head``, each projection role) and each Pallas kernel its own name.
"""

import json
import re

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
from repro.launch.mixer import Mixer, Request
from repro.models.transformer import Model
from repro.obs import metrics as omet
from repro.obs import trace as otr
from repro.runtime import inject
from repro.runtime.guard import HealthReport, guarded_generate

FAST = CoSearchConfig(objective="edp",
                      engine=EngineConfig(max_levels=2,
                                          max_allocs_per_pattern=16),
                      spatial_top=2, max_pairs=6)


def _cfg():
    return get_config("chatglm3-6b").reduced()


@pytest.fixture(scope="module")
def dense():
    cfg = _cfg()
    model = Model(cfg)
    return cfg, model, model.init(jax.random.key(0))


@pytest.fixture(scope="module")
def serving():
    """(cfg, model, plan, pruned, store) for an all-bitmap plan."""
    cfg = _cfg()
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    plan = rexec.build_exec_plan(cfg, BlockBernoulli(0.5, 32 * 32),
                                 tokens=64, search_cfg=FAST, value_bits=32)
    pruned = rexec.prune_params(params, plan, cfg)
    store = rexec.compress_params(pruned, plan, cfg)
    return cfg, model, plan, pruned, store


def _stream(cfg, plens, max_new, seed=0, **kw):
    rng = np.random.default_rng(seed)
    return [Request(uid=f"r{i}",
                    prompt=jnp.asarray(
                        rng.integers(1, cfg.vocab, (p,)), jnp.int32),
                    max_new=max_new[i] if isinstance(max_new, list)
                    else max_new, **kw)
            for i, p in enumerate(plens)]


# ---------------------------------------------------------------------------
# tracer core
# ---------------------------------------------------------------------------

def test_span_nesting_orders_events_and_mismatched_end_raises():
    tr = otr.Tracer()
    with otr.tracing(tr):
        with otr.span("outer", x=1):
            assert tr.depth == 1
            with otr.span("inner"):
                otr.event("mark", k=2)
                assert tr.depth == 2
        assert tr.depth == 0
    assert [e["ph"] for e in tr.events] == ["B", "B", "i", "E", "E"]
    assert [e["name"] for e in tr.events] == \
        ["outer", "inner", "mark", "inner", "outer"]
    assert tr.events[0]["args"] == {"x": 1}
    tr.begin("open")
    with pytest.raises(RuntimeError, match="does not match"):
        tr.end("outer")


def test_off_surface_is_a_shared_noop():
    assert otr.current_tracer() is None
    assert omet.current_metrics() is None
    s1, s2 = otr.span("a", x=1), otr.span("b")
    assert s1 is s2                           # the shared _NULL instance
    with s1:
        otr.event("nothing", v=3)
    assert otr.trace_id() is None and otr.trace_id("req9") is None
    # metrics module functions are silent no-ops too
    omet.counter_inc("c_total", 2.0)
    omet.gauge_set("g", 1.0)
    omet.observe("h_seconds", 0.5)


def test_chrome_trace_schema_valid():
    tr = otr.Tracer()
    with otr.tracing(tr):
        with otr.span("phase", batch=2):
            otr.event("mark", pos=3)
    doc = tr.chrome_trace()
    json.loads(json.dumps(doc))               # fully serializable
    assert doc["displayTimeUnit"] == "ms"
    rows = doc["traceEvents"]
    assert [r["ph"] for r in rows] == ["B", "i", "E"]
    for r in rows:
        assert set(r) >= {"name", "ph", "ts", "pid", "tid"}
        assert r["ts"] >= 0.0
    assert sum(r["ph"] == "B" for r in rows) == \
        sum(r["ph"] == "E" for r in rows)
    assert rows[1]["s"] == "t"                # instants carry scope


def test_stable_trace_drops_timings_and_unstable_events(tmp_path):
    tr = otr.Tracer()
    with otr.tracing(tr):
        with otr.span("phase"):
            otr.event("kept", a=1)
            otr.event("spike", stable=False, dt_s=0.5)
    st = tr.stable_trace()
    assert [e["name"] for e in st] == ["phase", "kept", "phase"]
    assert all(set(e) == {"ph", "name", "args"} for e in st)
    chrome, stable = tmp_path / "t.json", tmp_path / "t.stable.json"
    tr.save_chrome(str(chrome))
    tr.save_stable(str(stable))
    assert json.loads(chrome.read_text())["traceEvents"]
    assert json.loads(stable.read_text()) == st


def test_trace_id_deterministic():
    tr = otr.Tracer()
    with otr.tracing(tr):
        assert otr.trace_id("req0") == "t:req0"
        assert otr.trace_id() == "t0001"
        assert otr.trace_id() == "t0002"


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_registry_schema_and_values():
    reg = omet.MetricsRegistry()
    reg.counter_inc("req_total", 1.0, kind="a")
    reg.counter_inc("req_total", 2.0, kind="b")
    reg.counter_inc("req_total", 1.0, kind="a")
    assert reg.value("req_total", kind="a") == 2.0
    assert reg.total("req_total") == 4.0
    assert len(reg.series("req_total")) == 2
    reg.gauge_set("occ", 3.0)
    reg.gauge_set("occ", 1.0)                 # gauges overwrite
    assert reg.value("occ") == 1.0
    with pytest.raises(ValueError, match="only go up"):
        reg.counter_inc("req_total", -1.0)
    with pytest.raises(ValueError, match="is a counter"):
        reg.gauge_set("req_total", 5.0)


def test_histogram_le_semantics_and_snapshot_roundtrip():
    reg = omet.MetricsRegistry()
    for v in (0.5, 1.0, 3.0):                 # 1.0 lands in the le=1 bucket
        reg.observe("lat_seconds", v, buckets=(1.0, 2.0))
    snap = reg.snapshot()
    h = snap["histograms"]["lat_seconds"]
    assert h["buckets"] == {"1.0": 2, "2.0": 0, "+Inf": 1}
    assert h["count"] == 3 and h["sum"] == pytest.approx(4.5)
    assert json.loads(reg.to_json()) == json.loads(
        json.dumps(snap, sort_keys=True))


def test_prometheus_exposition_parses_with_cumulative_buckets():
    reg = omet.MetricsRegistry()
    reg.counter_inc("req_total", 2.0, code="ok")
    reg.gauge_set("occ", 3.0)
    for v in (0.5, 1.0, 3.0):
        reg.observe("lat_seconds", v, buckets=(1.0, 2.0))
    text = reg.prometheus_text()
    sample = re.compile(
        r'^[a-zA-Z_:][a-zA-Z0-9_:]*'
        r'(\{[a-zA-Z_][a-zA-Z0-9_]*="[^"]*"'
        r'(,[a-zA-Z_][a-zA-Z0-9_]*="[^"]*")*\})? \S+$')
    for line in text.strip().splitlines():
        if line.startswith("# TYPE "):
            continue
        assert sample.match(line), f"unparseable sample line: {line!r}"
    assert "# TYPE req_total counter" in text
    assert "# TYPE occ gauge" in text
    assert "# TYPE lat_seconds histogram" in text
    buckets = [float(ln.rsplit(" ", 1)[1]) for ln in text.splitlines()
               if ln.startswith("lat_seconds_bucket")]
    assert buckets == [2.0, 2.0, 3.0]         # cumulative, +Inf == count
    assert 'lat_seconds_count 3' in text


def test_ingest_instrument_equals_opcounters(serving):
    cfg, model, plan, pruned, store = serving
    cm = rexec.CompressedModel(model, store)
    tokens = jnp.asarray(np.arange(2 * 8).reshape(2, 8) % cfg.vocab,
                         jnp.int32)
    with rexec.instrument() as counters:
        cm.hidden_states(pruned, tokens)
    assert counters
    reg = omet.MetricsRegistry()
    omet.ingest_instrument(reg, counters)
    for role, c in counters.items():
        assert reg.value("exec_dispatch_calls_total", role=role) == c.calls
        assert reg.value("exec_w_fetch_bits_total",
                         role=role) == c.w_fetch_bits
        assert reg.value("exec_macs_total", role=role) == c.macs
        assert reg.value("exec_refetch_factor",
                         role=role) == pytest.approx(c.refetch_factor)


def test_ingest_health_equals_report_fields():
    rep = HealthReport(gen=8, steps=5, retries=2, dense_steps=3,
                       deadline_hit=True, eos_hit=True,
                       verify={"attn_qkv": "ok", "mlp_up": "bad_digest"})
    rep.record_fallback("mlp_up", "integrity_violation")
    rep.record_fallback("*", "deadline_exceeded")
    reg = omet.MetricsRegistry()
    omet.ingest_health(reg, rep)
    assert reg.value("serve_requests_total") == 1
    assert reg.value("serve_tokens_generated_total") == 5
    assert reg.value("serve_retries_total") == 2
    assert reg.value("serve_dense_steps_total") == 3
    assert reg.value("serve_deadline_hits_total") == 1
    assert reg.value("serve_eos_hits_total") == 1
    assert reg.value("serve_fallbacks_total",
                     code="integrity_violation") == 1
    assert reg.value("serve_fallbacks_total", code="deadline_exceeded") == 1
    assert reg.value("serve_verify_failures_total", role="mlp_up") == 1
    with pytest.raises(KeyError):
        reg.value("serve_verify_failures_total", role="attn_qkv")


def test_collect_caches_matches_sources():
    from repro.core import memo
    from repro.kernels import ops as kops
    reg = omet.MetricsRegistry()
    omet.collect_caches(reg)
    kc = kops.kernel_cache_stats()
    assert reg.value("kernel_cache_hits_total") == kc["hits"]
    assert reg.value("kernel_cache_misses_total") == kc["misses"]
    assert reg.value("kernel_cache_entries") == kc["entries"]
    for name, st in memo.stats().items():
        if st.lookups:
            assert reg.value("memo_hits_total", cache=name) == st.hits
            assert reg.value("memo_misses_total", cache=name) == st.misses


# ---------------------------------------------------------------------------
# HealthReport projections
# ---------------------------------------------------------------------------

def test_health_report_stable_plus_timings_is_to_dict():
    rep = HealthReport(gen=4, steps=4, request_id="r1", trace_id="t:r1",
                       t_prefill_s=0.5, t_decode_s=1.5, t_total_s=2.0)
    rep.record_fallback("attn_qkv", "kernel_failure")
    assert rep.stable_dict() | rep.timings_dict() == rep.to_dict()
    assert "t_decode_s" not in rep.stable_dict()
    assert rep.stable_dict()["trace_id"] == "t:r1"
    assert set(rep.timings_dict()) == {"t_prefill_s", "t_decode_s",
                                       "t_total_s"}
    assert HealthReport.from_dict(rep.to_dict()) == rep
    assert HealthReport.from_json(rep.to_json()) == rep


def test_trace_id_none_when_untraced(dense):
    cfg, model, params = dense
    prompts = jnp.asarray(np.arange(2 * 4).reshape(2, 4) % cfg.vocab,
                          jnp.int32)
    _, rep = guarded_generate(model, params, prompts, 2, 8, verify=False)
    assert rep.trace_id is None
    assert "trace_id" in rep.stable_dict()


# ---------------------------------------------------------------------------
# serving integration: mixer
# ---------------------------------------------------------------------------

def _mixer_run(cfg, model, params, sampled: bool):
    kw = {"temperature": 0.8, "top_k": 8} if sampled else {}
    reqs = _stream(cfg, [6, 3, 5, 2], [4, 2, 3, 2], **kw)
    tracer = otr.Tracer()
    reg = omet.MetricsRegistry()
    with otr.tracing(tracer), omet.collecting(reg):
        mx = Mixer(model, params, slots=2, max_len=16)
        results = mx.run(reqs)
    return tracer, reg, results


@pytest.mark.parametrize("sampled", [False, True],
                         ids=["greedy", "sampled"])
def test_mixer_stable_trace_deterministic_across_runs(dense, sampled):
    cfg, model, params = dense
    tr1, _, res1 = _mixer_run(cfg, model, params, sampled)
    tr2, _, res2 = _mixer_run(cfg, model, params, sampled)
    assert tr1.stable_trace() == tr2.stable_trace()
    for a, b in zip(res1, res2):
        assert a.report.stable_dict() == b.report.stable_dict()


def test_mixer_trace_linkage_and_counter_parity(dense):
    cfg, model, params = dense
    tracer, reg, results = _mixer_run(cfg, model, params, sampled=False)
    uids = {r.uid for r in results}
    for res in results:
        assert res.report.trace_id == f"t:{res.uid}"
        named = [e for e in tracer.events
                 if e["args"].get("trace_id") == res.report.trace_id]
        kinds = {(e["ph"], e["name"]) for e in named}
        assert {("B", "admit"), ("B", "prefill"), ("B", "slot_write"),
                ("i", "token"), ("i", "evict")} <= kinds
        toks = [e for e in named if e["name"] == "token"]
        assert len(toks) == res.report.steps
    evicts = [e for e in tracer.events if e["name"] == "evict"]
    assert {e["args"]["request_id"] for e in evicts} == uids
    # live mixer counters and per-report ingestion agree with the reports
    assert reg.value("mixer_admissions_total") == len(results)
    assert reg.total("mixer_evictions_total") == len(results)
    assert reg.value("serve_requests_total") == len(results)
    assert reg.value("serve_tokens_generated_total") == \
        sum(r.report.steps for r in results)
    assert reg.value("mixer_slot_occupancy") == 0   # drained at the end
    # every decode step recorded its latency
    assert reg.value("mixer_decode_steps_total") > 0


def test_mixer_straggler_lands_in_stats_and_snapshot(dense):
    from repro.runtime.fault import StragglerMonitor
    cfg, model, params = dense
    mon = StragglerMonitor(threshold=0.0, warmup=0)   # flag every step
    reqs = _stream(cfg, [4, 3], 2)
    reg = omet.MetricsRegistry()
    tracer = otr.Tracer()
    with otr.tracing(tracer), omet.collecting(reg):
        mx = Mixer(model, params, slots=2, max_len=8, straggler=mon)
        mx.run(reqs)
    st = mx.stats()
    assert st["straggler_spikes"] == len(mon.flagged) > 0
    assert st["step_ewma_s"] == mon.ewma
    assert reg.value("mixer_straggler_spikes_total") == len(mon.flagged)
    omet.ingest_straggler(reg, mon)
    assert reg.value("straggler_ewma_seconds") == pytest.approx(mon.ewma)
    # spikes are timing-derived: visible in the raw trace, NOT the stable
    # projection, and never in Mixer.events (the CI determinism surface)
    assert any(e["name"] == "straggler_spike" for e in tracer.events)
    assert not any(e["name"] == "straggler_spike"
                   for e in tracer.stable_trace())
    assert not any(ev.get("event") == "straggler_spike" for ev in mx.events)


# ---------------------------------------------------------------------------
# serving integration: off-switch + guarded path
# ---------------------------------------------------------------------------

def test_telemetry_off_and_on_leave_tokens_bit_identical(dense):
    cfg, model, params = dense
    prompts = jnp.asarray(np.arange(2 * 6).reshape(2, 6) % cfg.vocab,
                          jnp.int32)
    toks_off, _, _ = serve.generate(model, params, prompts, 3, 12)
    with otr.tracing(otr.Tracer()) as tr, \
            omet.collecting(omet.MetricsRegistry()) as reg:
        toks_on, _, _ = serve.generate(model, params, prompts, 3, 12)
    toks_off2, _, _ = serve.generate(model, params, prompts, 3, 12)
    np.testing.assert_array_equal(np.asarray(toks_off), np.asarray(toks_on))
    np.testing.assert_array_equal(np.asarray(toks_off), np.asarray(toks_off2))
    # the traced run actually recorded the serving spans
    names = {e["name"] for e in tr.events}
    assert {"prefill", "decode"} <= names
    assert reg.value("serve_static_tokens_total") == 2 * 3


def test_guarded_traced_run_emits_demote_and_matching_counters(serving):
    cfg, model, plan, pruned, store = serving
    role = next(op.role for op in plan.ops if op.choice.kind == "bitmap")
    bad = inject.bitflip_payload(store, role, seed=3)
    cm = rexec.CompressedModel(model, bad)
    prompts = jnp.asarray(np.arange(2 * 6).reshape(2, 6) % cfg.vocab,
                          jnp.int32)
    tracer = otr.Tracer()
    reg = omet.MetricsRegistry()
    with otr.tracing(tracer), omet.collecting(reg):
        toks, report = guarded_generate(cm, pruned, prompts, 3, 12)
    assert report.trace_id == "t0001"
    assert report.verify[role] == "checksum_mismatch"
    demotes = [e for e in tracer.events if e["name"] == "demote"]
    assert [d["args"]["role"] for d in demotes] == [role]
    assert demotes[0]["args"]["code"] == "integrity_violation"
    assert demotes[0]["args"]["trace_id"] == report.trace_id
    # the demote survives into the stable projection (it is stream-
    # determined, not timing-derived)
    assert any(e["name"] == "demote" for e in tracer.stable_trace())
    spans = {e["name"] for e in tracer.events if e["ph"] == "B"}
    assert {"guarded_request", "verify", "prefill", "decode"} <= spans
    assert reg.value("serve_verify_failures_total", role=role) == 1
    assert reg.value("serve_fallbacks_total", code="integrity_violation") \
        == report.fallback_counts()["integrity_violation"]
    assert reg.value("serve_tokens_generated_total") == report.steps


# ---------------------------------------------------------------------------
# the profiler's clock: spans as TraceAnnotations, device work by scope
# ---------------------------------------------------------------------------

def _host_events(log_dir) -> list:
    """(name, start_ns, end_ns, stats) of every host-plane event in the
    one profile written under ``log_dir``."""
    import glob
    from jax.profiler import ProfileData
    (path,) = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    return [(ev.name, ev.start_ns, ev.end_ns, dict(ev.stats))
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events]


def _capture(log_dir, fn):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        return fn()
    finally:
        jax.profiler.stop_trace()


def test_span_is_a_trace_annotation_only_while_a_profiler_records(tmp_path):
    assert otr.current_tracer() is None
    assert otr.span("before", k=1) is otr.span("other")   # shared no-op

    def traced():
        s = otr.span("profiled", step=3, trace_id=None)
        assert s is not otr.span("other")
        with s:
            with otr.span("profiled.child"):
                pass
        tracer = otr.Tracer()
        with otr.tracing(tracer), otr.span("both", x=2):
            pass
        return tracer
    tracer = _capture(tmp_path, traced)
    assert otr.span("after") is otr.span("other")
    with otr.span("after"):
        pass
    ev = {name: (a, b, st) for name, a, b, st in _host_events(tmp_path)}
    assert "before" not in ev and "after" not in ev
    assert ev["profiled"][2] == {"step": 3}     # args as stats, None dropped
    a, b, _ = ev["profiled"]
    ca, cb, _ = ev["profiled.child"]
    assert a <= ca <= cb <= b
    assert ev["both"][2] == {"x": 2}           # profiler and tracer alike
    assert [e["name"] for e in tracer.events] == ["both", "both"]


def _scope_parts(text: str) -> set:
    return {part for name in re.findall(r'op_name="([^"]*)"', text)
            for part in name.split("/")}


def test_compiled_decode_step_carries_the_program_scopes(serving):
    """The name scopes reach the compiled decode step's ``op_name``
    metadata, which the profiler reports each device op under."""
    cfg, model, plan, pruned, store = serving
    cm = rexec.CompressedModel(model, store)
    cache = cm.init_cache(2, 16)
    lowered = jax.jit(cm.decode_step).lower(
        pruned, cache, jnp.zeros(2, jnp.int32), jnp.zeros(2, jnp.int32))
    parts = _scope_parts(lowered.compile().as_text())
    roles = {op.role for op in plan.ops}
    assert {"decode", "attention", "kv_write", "head"} <= parts
    assert roles <= parts and "ffn.w_down" in roles
    assert parts & {"bitmap_spmm", "bitmap_spmm_pipelined"}


def test_compiled_prefill_and_slot_write_carry_their_scopes(serving):
    from repro.launch.mixer import write_slot
    cfg, model, plan, pruned, store = serving
    cm = rexec.CompressedModel(model, store)
    tokens = jnp.zeros((1, 8), jnp.int32)
    prefill = jax.jit(cm.prefill, static_argnums=2).lower(pruned, tokens, 16)
    parts = _scope_parts(prefill.compile().as_text())
    assert {"prefill", "attention"} <= parts
    assert {op.role for op in plan.ops} <= parts
    cache, row = cm.init_cache(2, 16), cm.init_cache(1, 16)
    write = jax.jit(write_slot).lower(cache, row, jnp.asarray(1, jnp.int32))
    assert "slot_write" in _scope_parts(write.compile().as_text())


@pytest.mark.parametrize("kernel", ["bitmap_spmm", "bitmap_spmm_pipelined",
                                    "nm_spmm", "nm_spmm_pipelined",
                                    "flash_attention"])
def test_each_pallas_kernel_is_named_after_itself(kernel):
    from repro.kernels import ops as kops
    rng = np.random.default_rng(0)
    w = rng.standard_normal((256, 256)).astype(np.float32)
    x = jnp.asarray(rng.standard_normal((8, 256)), jnp.float32)
    if kernel.startswith("bitmap"):
        w[:128, :128] = 0.0
        wc = kops.compress_bitmap(w, bn=128, bk=128)
        pipeline = kernel.endswith("pipelined")
        fn = lambda x: kops.bitmap_spmm(x, wc, pipeline=pipeline)  # noqa: E731
    elif kernel.startswith("nm"):
        wc = kops.compress_nm(w)
        pipeline = kernel.endswith("pipelined")
        fn = lambda x: kops.nm_spmm(x, wc, pipeline=pipeline)  # noqa: E731
    else:
        q = jnp.asarray(rng.standard_normal((2, 128, 64)), jnp.float32)
        fn = lambda x: kops.flash_attention(q, q, q)  # noqa: E731
    text = jax.jit(fn).lower(x).as_text(dialect="hlo", debug_info=True)
    assert kernel in _scope_parts(text)
