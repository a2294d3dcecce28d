"""Opt-in profiling hooks: jax.profiler capture + kernel-dispatch timing.

Two layers, both off by default and free when off:

  * :func:`jax_trace` — wraps a region in a ``jax.profiler`` trace capture
    (TensorBoard/Perfetto-loadable artifacts under ``log_dir``).  A no-op
    when ``log_dir`` is falsy; when a trace was asked for and the profiler
    cannot start, it raises — a run that was meant to be traced does not
    quietly go on untraced.
  * :func:`kernel_timer` — installs a
    :func:`repro.kernels.ops.kernel_dispatch_hook` (the observation twin
    of the fault-injection ``kernel_fault_hook``) that records every
    sparse-kernel dispatch into the ambient metrics registry
    (``kernel_dispatch_total{kind=}`` counter +
    ``kernel_dispatch_seconds`` histogram) and as ``X`` complete events
    in the ambient trace.  Dispatch happens at TRACE time under jit, so
    warm cache hits record nothing — the hook measures what a forward
    actually pays, which is exactly the jit-cache contract the serving
    plane is built on.
"""

from __future__ import annotations

import contextlib
from typing import Iterator, Optional

from repro.obs import metrics as _metrics
from repro.obs import trace as _trace


@contextlib.contextmanager
def jax_trace(log_dir: Optional[str]) -> Iterator[None]:
    """Capture a ``jax.profiler`` trace of the region into ``log_dir``
    (no-op when ``log_dir`` is None/empty).  A profiler that cannot start
    raises from ``start_trace``."""
    if not log_dir:
        yield
        return
    import jax
    jax.profiler.start_trace(str(log_dir))
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def annotation(name: str):
    """A ``jax.profiler.TraceAnnotation`` naming a region inside a
    :func:`jax_trace` capture."""
    import jax
    return jax.profiler.TraceAnnotation(name)


@contextlib.contextmanager
def kernel_timer(registry: Optional[_metrics.MetricsRegistry] = None,
                 tracer: Optional[_trace.Tracer] = None) -> Iterator[None]:
    """Record every sparse-kernel dispatch while active.

    ``registry`` / ``tracer`` default to the AMBIENT ones at dispatch
    time, so ``kernel_timer()`` composes with :func:`repro.obs.metrics
    .collecting` / :func:`repro.obs.trace.tracing` without re-plumbing.
    Trace events are complete (``X``) events named ``kernel:<kind>`` —
    their wall-clock is timing-derived, so they are excluded from
    :meth:`~repro.obs.trace.Tracer.stable_trace`."""
    from repro.kernels import ops as kops

    def hook(kind: str, dt: float) -> None:
        reg = registry if registry is not None else \
            _metrics.current_metrics()
        if reg is not None:
            reg.counter_inc("kernel_dispatch_total", 1.0, kind=kind)
            reg.observe("kernel_dispatch_seconds", dt, kind=kind)
        tr = tracer if tracer is not None else _trace.current_tracer()
        if tr is not None:
            tr.complete(f"kernel:{kind}", dt, {"kind": kind}, stable=False)

    with kops.kernel_dispatch_hook(hook):
        yield
