"""Guarded serving: verify → demote → retry → degrade to dense.

:func:`guarded_generate` wraps the serving driver's prefill + greedy decode
loop (:mod:`repro.launch.serve`) with the robustness layer:

  1. **Verify before dispatch** — both store representations
     (:meth:`CompressedStore.verify` / :meth:`StackedStore.verify`); roles
     that fail are demoted to dense weights (``CompressedModel.demoted``)
     and recorded as ``integrity_violation`` fallbacks.  One corrupt role
     costs its compression ratio, not the batch.
  2. **Kernel-failure guard** — :func:`repro.exec.dispatch.kernel_guard`
     turns kernel dispatch exceptions (real or injected) into per-role
     dense fallbacks at trace time, recorded as ``kernel_failure``.
  3. **Step guard** — every prefill/decode step runs under the (previously
     train-only) :class:`repro.runtime.fault.StepGuard`: bounded retry on
     runtime errors AND on non-finite logits (:class:`NonFiniteError`);
     persistent failure switches the request to the dense model for the
     REST of the generation (``nonfinite_logits`` / ``step_failure``).
     The decode step is jitted WITHOUT cache donation so the pre-step
     cache survives for the retry — that, plus the per-step finite check,
     is the guarded path's measured overhead (``bench_serve``'s
     ``serve_guarded_vs_unguarded`` row).
  4. **Deadline** — an optional per-request wall-clock budget checked each
     decode step; on expiry the tail is padded with ``pad_id`` and the
     report says so (``deadline_exceeded``).

Runtime fallbacks reuse the plan-time :class:`FallbackReason` machinery
with the runtime codes documented there.  Everything observable lands in
the :class:`HealthReport` returned alongside the tokens; its
:meth:`HealthReport.stable_dict` projection (timings dropped) is
deterministic for a fixed seed — CI diffs two guarded runs on it.

Dense fallbacks are CORRECT, not merely safe: serving runs on the pruned
parameter tree, so the dense einsum computes exactly what the compressed
kernel encodes — guarded greedy decode is bit-identical to dense at fp32
on bitmap plans, faults injected or not.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import time
from typing import Optional

import jax
import jax.numpy as jnp

from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.runtime import fault, integrity


class NonFiniteError(RuntimeError):
    """Logits came back NaN/Inf.  A ``RuntimeError`` so the train-plane
    :class:`~repro.runtime.fault.StepGuard` retries it like any other
    step failure."""


class _NoPrefill(Exception):
    """Internal: the model has no one-pass prefill (token-by-token ingest
    instead).  Deliberately NOT a RuntimeError — ``NotImplementedError``
    is one, and the StepGuard must not burn retries on a capability."""


@dataclasses.dataclass
class HealthReport:
    """Everything the guarded serving path observed for one request batch.

    ``fallbacks`` rows are ``{"role", "layer", "code", "detail"}`` with
    role ``"*"`` for whole-step events; ``verify`` maps each planned role
    to ``"ok"`` or the :class:`IntegrityError` reason.
    ``switched_to_dense_at`` is the decode position where the request
    degraded to the dense model (``-1`` = during prefill, ``None`` =
    never).  Under mixed traffic (:mod:`repro.launch.mixer`) one report
    is produced PER REQUEST: ``request_id`` names it and ``eos_hit``
    records an EOS-terminated generation (``steps`` < ``gen`` with no
    deadline).  ``trace_id`` links the report to its spans in the active
    :class:`repro.obs.trace.Tracer` (None when tracing was off — the id
    is deterministic, derived from the request id or a tracer counter).
    Timings are wall-clock seconds; everything else is deterministic for
    a fixed seed — :meth:`stable_dict` drops the timings so two runs can
    be diffed exactly, and :meth:`timings_dict` is the complementary
    projection (``stable_dict() | timings_dict() == to_dict()``)."""

    verify: dict = dataclasses.field(default_factory=dict)
    fallbacks: list = dataclasses.field(default_factory=list)
    retries: int = 0
    dense_steps: int = 0
    switched_to_dense_at: Optional[int] = None
    deadline_hit: bool = False
    eos_hit: bool = False
    steps: int = 0
    gen: int = 0
    request_id: Optional[str] = None
    trace_id: Optional[str] = None
    t_prefill_s: float = 0.0
    t_decode_s: float = 0.0
    t_total_s: float = 0.0

    def record_fallback(self, role: str, code: str, detail: str = "",
                        layer: Optional[int] = None) -> None:
        self.fallbacks.append({"role": role, "layer": layer,
                               "code": code, "detail": detail})

    def fallback_counts(self) -> dict[str, int]:
        """Occurrences by reason code (same shape as
        :meth:`ExecPlan.fallback_counts`)."""
        out: dict[str, int] = {}
        for fb in self.fallbacks:
            out[fb["code"]] = out.get(fb["code"], 0) + 1
        return out

    def fallback_reasons(self) -> list:
        """The fallbacks as plan-plane :class:`FallbackReason` values."""
        from repro.exec.plans import FallbackReason
        return [FallbackReason(fb["code"], fb["detail"])
                for fb in self.fallbacks]

    @property
    def healthy(self) -> bool:
        """No fallbacks, no retries, nothing non-ok in verify."""
        return (not self.fallbacks and not self.retries
                and not self.deadline_hit
                and all(v == "ok" for v in self.verify.values()))

    @property
    def latency_per_token_s(self) -> float:
        return self.t_decode_s / self.steps if self.steps else 0.0

    # -- JSON ---------------------------------------------------------------
    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    _TIMING_KEYS = ("t_prefill_s", "t_decode_s", "t_total_s")

    def stable_dict(self) -> dict:
        """The deterministic projection: everything except wall-clock."""
        out = self.to_dict()
        for k in self._TIMING_KEYS:
            del out[k]
        return out

    def timings_dict(self) -> dict:
        """The wall-clock half :meth:`stable_dict` drops, structured:
        ``stable_dict() | timings_dict()`` reconstructs :meth:`to_dict`
        exactly (round-trip pinned in ``tests/test_obs.py``)."""
        return {k: getattr(self, k) for k in self._TIMING_KEYS}

    def to_json(self, indent: Optional[int] = 1) -> str:
        return json.dumps(self.to_dict(), indent=indent)

    @staticmethod
    def from_dict(d: dict) -> "HealthReport":
        return HealthReport(**d)

    @staticmethod
    def from_json(s: str) -> "HealthReport":
        return HealthReport.from_dict(json.loads(s))


def _finite(x: jax.Array) -> bool:
    return bool(jnp.isfinite(x).all())


def _failure_code(error_repr: str) -> str:
    return "nonfinite_logits" if "NonFiniteError" in error_repr \
        else "step_failure"


def guarded_generate(model, params, prompts: jax.Array, gen: int,
                     max_len: Optional[int] = None, *,
                     dense_model=None, verify: bool = True,
                     deadline_s: Optional[float] = None,
                     max_retries: int = 1, pad_id: int = -1,
                     eos_id: Optional[int] = None,
                     mesh=None) -> tuple[jax.Array, HealthReport]:
    """Greedy batched generation with the full robustness layer.

    ``model`` is a :class:`CompressedModel` (the usual case) or a dense
    ``Model``; ``dense_model`` is the degradation target (defaults to the
    compressed model's own inner dense model — correct because serving
    runs on the pruned tree).  Returns ``(tokens (B, gen) int32,
    HealthReport)``; positions not produced before ``deadline_s`` hold
    ``pad_id``.  With ``eos_id``, a row's tokens after its EOS hold
    ``pad_id`` and decode stops early once EVERY row has emitted EOS
    (``report.eos_hit``) instead of burning the remaining steps."""
    from repro.exec.dispatch import CompressedModel
    from repro.launch.mesh import axis_map_for
    from repro.models.sharding import logical_axis_rules, named_sharding

    t_start = time.perf_counter()
    tid = obs_trace.trace_id()
    report = HealthReport(gen=gen, trace_id=tid)
    if max_len is None:
        max_len = prompts.shape[1] + gen

    cm = model
    compressed = isinstance(model, CompressedModel)
    if compressed and dense_model is None:
        dense_model = model.model
    with obs_trace.span("guarded_request", trace_id=tid,
                        batch=int(prompts.shape[0]), gen=gen,
                        compressed=compressed):
        if compressed and verify:
            statuses: dict[str, str] = {}
            errors: dict[str, integrity.IntegrityError] = {}
            with obs_trace.span("verify", trace_id=tid):
                for source in (cm.store, cm.stacked):
                    for role, err in integrity.role_errors(source):
                        statuses.setdefault(role, "ok")
                        if err is not None and statuses[role] == "ok":
                            statuses[role] = err.reason
                            errors[role] = err
            report.verify = statuses
            if errors:
                for role in sorted(errors):
                    err = errors[role]
                    report.record_fallback(role, "integrity_violation",
                                           detail=err.reason, layer=err.layer)
                    obs_trace.event("demote", trace_id=tid, role=role,
                                    code="integrity_violation",
                                    reason=err.reason)
                cm = cm.demoted(errors)

        if mesh is not None:
            with mesh, logical_axis_rules(axis_map_for(mesh), mesh=mesh):
                prompts = jax.device_put(prompts,
                                         named_sharding(mesh, "batch", None))
                toks = _drive(cm, dense_model, params, prompts, gen, max_len,
                              report, deadline_s, max_retries, pad_id,
                              t_start, compressed, eos_id)
        else:
            toks = _drive(cm, dense_model, params, prompts, gen, max_len,
                          report, deadline_s, max_retries, pad_id, t_start,
                          compressed, eos_id)
    report.t_total_s = time.perf_counter() - t_start
    reg = obs_metrics.current_metrics()
    if reg is not None:
        obs_metrics.ingest_health(reg, report)
    return toks, report


def _drive(cm, dense, params, prompts, gen: int, max_len: int,
           report: HealthReport, deadline_s: Optional[float],
           max_retries: int, pad_id: int, t_start: float,
           compressed: bool, eos_id: Optional[int] = None) -> jax.Array:
    import contextlib

    import numpy as np

    from repro.exec.dispatch import jit_serving, kernel_guard

    b, plen = prompts.shape
    tid = report.trace_id
    demoted_roles: set[str] = set()

    def sink(role: str, exc: Exception) -> None:
        # trace-time kernel failures re-report per traced function; one
        # fallback row per role is the useful signal
        if role not in demoted_roles:
            demoted_roles.add(role)
            report.record_fallback(role, "kernel_failure", detail=repr(exc))
            obs_trace.event("demote", trace_id=tid, role=role,
                            code="kernel_failure")

    # the pre-step cache must survive a retry AND the dense fallback's
    # re-step, so — unlike the unguarded driver — no donate_argnums here
    step_c = jit_serving(cm, cm.decode_step)
    step_d = None
    if dense is not None and dense is not cm:
        step_d = jit_serving(dense, dense.decode_step)
    guard = fault.StepGuard(max_retries=max_retries,
                            on_restore=lambda: None)
    dense_guard = fault.StepGuard(max_retries=max_retries,
                                  on_restore=lambda: None)
    use_dense = False

    def attempt(fn, cache, tok, pos: int):
        lg, nc = fn(params, cache, tok, jnp.asarray(pos, jnp.int32))
        if not _finite(lg):
            raise NonFiniteError(f"non-finite logits at position {pos}")
        return lg, nc

    def _note_retries(g, n0: int, pos: int) -> None:
        for ev in g.events[n0:]:
            if ev.action == "retry":
                obs_trace.event("retry", trace_id=tid, pos=pos,
                                code=_failure_code(ev.error))

    def guarded_step(pos: int, cache, tok):
        nonlocal use_dense
        if not use_dense:
            n0 = len(guard.events)
            res = guard.run(pos, lambda: attempt(step_c, cache, tok, pos))
            _note_retries(guard, n0, pos)
            if res is not None:
                return res
            last = guard.events[-1].error
            if step_d is None:
                raise RuntimeError(
                    f"guarded decode failed at position {pos} with no "
                    f"dense fallback available: {last}")
            use_dense = True
            report.switched_to_dense_at = pos
            report.record_fallback("*", _failure_code(last), detail=last)
            obs_trace.event("dense_switch", trace_id=tid, pos=pos,
                            code=_failure_code(last))
        n1 = len(dense_guard.events)
        res = dense_guard.run(pos, lambda: attempt(step_d, cache, tok, pos))
        _note_retries(dense_guard, n1, pos)
        if res is None:
            raise RuntimeError(
                f"dense fallback failed at position {pos}: "
                f"{dense_guard.events[-1].error}")
        report.dense_steps += 1
        return res

    guard_ctx = kernel_guard(sink) if compressed else contextlib.nullcontext()
    with guard_ctx:
        # ---- prefill (guarded; falls back to guarded token ingest) --------
        with obs_trace.span("prefill", trace_id=tid, batch=b, plen=plen):
            t0 = time.perf_counter()
            prefill_c = jit_serving(
                cm, functools.partial(cm.prefill, max_len=max_len))

            def attempt_prefill():
                try:
                    all_lg, c = prefill_c(params, prompts)
                except NotImplementedError as e:
                    raise _NoPrefill() from e
                lg = all_lg[:, -1]
                if not _finite(lg):
                    raise NonFiniteError("non-finite prefill logits")
                return lg, c

            try:
                n0 = len(guard.events)
                res = guard.run(-1, attempt_prefill)
                _note_retries(guard, n0, -1)
                if res is None:
                    last = guard.events[-1].error
                    if step_d is None:
                        raise RuntimeError(
                            f"guarded prefill failed with no dense fallback "
                            f"available: {last}")
                    use_dense = True
                    report.switched_to_dense_at = -1
                    report.record_fallback("*", _failure_code(last),
                                           detail=last)
                    obs_trace.event("dense_switch", trace_id=tid, pos=-1,
                                    code=_failure_code(last))
                    prefill_d = jit_serving(dense, functools.partial(
                        dense.prefill, max_len=max_len))
                    all_lg, cache = prefill_d(params, prompts)
                    logits = all_lg[:, -1]
                    if not _finite(logits):
                        raise NonFiniteError(
                            "dense prefill logits non-finite")
                else:
                    logits, cache = res
            except _NoPrefill:
                # ring windows / hybrid / ssm / encdec: exact decode-path
                # ingest, every step under the same guard
                cache = cm.init_cache(b, max_len)
                logits = None
                for t in range(plen):
                    logits, cache = guarded_step(t, cache, prompts[:, t])
            jax.block_until_ready(logits)
            report.t_prefill_s = time.perf_counter() - t0

        # ---- greedy decode ------------------------------------------------
        with obs_trace.span("decode", trace_id=tid, batch=b, gen=gen):
            out = []
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            done = np.zeros(b, bool)      # rows that already emitted EOS
            t1 = time.perf_counter()
            for t in range(plen, plen + gen):
                if deadline_s is not None and \
                        time.perf_counter() - t_start > deadline_s:
                    report.deadline_hit = True
                    report.record_fallback(
                        "*", "deadline_exceeded",
                        detail=f"{len(out)}/{gen} tokens within "
                               f"{deadline_s}s")
                    obs_trace.event("deadline", trace_id=tid, pos=t)
                    break
                if eos_id is None:
                    out.append(tok)
                else:
                    # the EOS token itself is emitted; everything AFTER a
                    # row's EOS holds pad_id (the deadline tail's
                    # semantics), and once every row is done the remaining
                    # steps are skipped entirely instead of decoded and
                    # discarded
                    out.append(jnp.where(jnp.asarray(done), pad_id, tok))
                    done |= np.asarray(tok) == eos_id
                    if done.all():
                        report.eos_hit = True
                        break
                logits, cache = guarded_step(t, cache, tok)
                tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            if out:
                jax.block_until_ready(out[-1])
            report.t_decode_s = time.perf_counter() - t1

    report.steps = len(out)
    report.retries = sum(1 for e in guard.events if e.action == "retry") + \
        sum(1 for e in dense_guard.events if e.action == "retry")
    if len(out) < gen:
        pad = jnp.full((b,), pad_id, jnp.int32)
        out.extend([pad] * (gen - len(out)))
    return jnp.stack(out, axis=1)
