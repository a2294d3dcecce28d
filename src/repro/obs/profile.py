"""Opt-in ``jax.profiler`` capture.

:func:`jax_trace` wraps a region in a ``jax.profiler`` trace capture
(TensorBoard/Perfetto-loadable artifacts under ``log_dir``).  A no-op when
``log_dir`` is falsy; when a trace was asked for and the profiler cannot
start, it raises — a run that was meant to be traced does not quietly go
on untraced.

Inside a capture the program names its own work: every
:func:`repro.obs.trace.span` is a ``TraceAnnotation`` on the host, the
device ops carry the ``jax.named_scope`` path of the code that made them
(``decode``, ``prefill``, ``slot_write``, ``attention``, ``kv_write``,
``head`` and each projection's role), and each Pallas kernel is named
after itself (``bitmap_spmm``, ``nm_spmm``, ``flash_attention``, …).
"""

from __future__ import annotations

import contextlib
from typing import Iterator, Optional


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
