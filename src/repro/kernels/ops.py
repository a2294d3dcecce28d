"""Jit'd public wrappers for the sparse kernels.

On the CPU backend (the test suite) the Pallas kernels run in
``interpret=True`` mode; on TPU Mosaic compiles them; any other backend
raises.  Compression runs host-side (numpy) — it is the SnipSnap format
decoder's software half: the chosen format's metadata becomes
scalar-prefetch arrays whose layout mirrors the kernel tiling.

Row tiles follow :mod:`repro.kernels.tiling`: the wrappers pad the
activation's rows to a multiple of a sublane-aligned tile inside the jitted
call and slice the padding off the output, so any row count — one decode
slot, a 37-token prompt — runs on tiles Mosaic accepts.

The jitted wrappers are CACHED per static-knob tuple (``_jitted``): the
seed rebuilt ``jax.jit(functools.partial(...))`` on every call, which made
every invocation a fresh jit object and threw away XLA's compile cache —
repeated layers of a served model each paid a retrace.  Now the partial is
built once per (kernel, static args) key and jax's own per-shape cache does
the rest; :func:`kernel_cache_stats` exposes hit counters so tests can pin
that the second call of a shape reuses the first's compilation.

Cache keying, precisely: every knob that changes the compiled grid or body
is in the key — for bitmap that is ``(k, bm, t_max, pipeline, interpret)``,
for N:M ``(n_sel, m_group, bm, bn, bk, pipeline, interpret)``.  The
``t_max`` entry is what lets the scanned serving path and the unrolled
per-layer loop SHARE entries: both dispatch with the per-role
across-layers max (the scanned path because the stacked store pads every
layer to one bound, the unrolled path because ``_Dispatcher`` pre-computes
the same max), so the key tuples coincide.  Dispatching a role with a
per-layer ``t_max`` instead would fork one cache entry per distinct layer
bound and silently recompile under scan — the regression test
``test_kernel_cache_shared_between_scanned_and_unrolled`` pins the shared
count.  ``pipeline`` is in the key even though the streaming kernel
ignores ``t_max``: two wrappers differing only in path choice must never
alias.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref, tiling
from repro.kernels.bitmap_spmm import bitmap_spmm_pallas
from repro.kernels.nm_spmm import nm_spmm_pallas


def _interpret() -> bool:
    """Interpret mode on the CPU backend, Mosaic on TPU; no other backend
    has a kernel path, and running one in the interpreter there would pass
    for a device run."""
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(
            f"no Pallas kernel path for the {backend!r} backend: the sparse "
            f"kernels compile for TPU and run in interpret mode on CPU only")
    return backend == "cpu"


_PIPELINE_DEFAULT = True


def resolve_pipeline(pipeline: bool | None) -> bool:
    """Resolve the dispatch-level ``pipeline`` knob (None → default).

    The double-buffered streaming path is the default on every backend: on
    TPU it overlaps HBM→VMEM payload DMAs with the MXU, and even the
    interpret-mode discharge on CPU wins because its per-``kj`` loop walks
    only ``counts[kj]`` real blocks instead of the naive path's full
    ``t_max`` grid steps.  ``pipeline=False`` keeps the seed's naive
    BlockSpec-driven kernels for parity tests and benchmarks."""
    return _PIPELINE_DEFAULT if pipeline is None else bool(pipeline)


@contextlib.contextmanager
def pipeline_default(on: bool):
    """Temporarily change what ``pipeline=None`` resolves to.

    Lets whole execution paths that never thread the knob (the serving
    dispatchers) be timed against the naive kernels — both settings share
    the jit-wrapper cache because the RESOLVED value is what enters the
    key."""
    global _PIPELINE_DEFAULT
    prev = _PIPELINE_DEFAULT
    _PIPELINE_DEFAULT = bool(on)
    try:
        yield
    finally:
        _PIPELINE_DEFAULT = prev


# ---------------------------------------------------------------------------
# Kernel fault hook (deterministic failure injection for robustness tests)
# ---------------------------------------------------------------------------

_FAULT_HOOK = None


@contextlib.contextmanager
def kernel_fault_hook(fn):
    """Install a hook called as ``fn(kind)`` at every sparse-kernel dispatch
    (``kind`` ∈ {"bitmap", "nm"}) — raising from the hook simulates a kernel
    failure at trace/dispatch time, which is where a real lowering or launch
    failure surfaces.  The serving dispatchers' ``kernel_guard`` turns such
    failures into per-role dense fallbacks; :mod:`repro.runtime.inject`
    builds its ``kernel_failure`` harness on this hook."""
    global _FAULT_HOOK
    prev = _FAULT_HOOK
    _FAULT_HOOK = fn
    try:
        yield
    finally:
        _FAULT_HOOK = prev


def _fault_check(kind: str) -> None:
    if _FAULT_HOOK is not None:
        _FAULT_HOOK(kind)


# ---------------------------------------------------------------------------
# Kernel dispatch hook (per-dispatch timing for the observability plane)
# ---------------------------------------------------------------------------

_DISPATCH_HOOK = None


@contextlib.contextmanager
def kernel_dispatch_hook(fn):
    """Install a hook called as ``fn(kind, seconds)`` after every kernel
    dispatch (``kind`` ∈ {"bitmap", "nm", "flash"}) — the observation twin
    of :func:`kernel_fault_hook`.  Under jit the dispatch runs at TRACE
    time, so a warm cache hit never reaches the hook; what it times is the
    dispatch/trace cost a forward actually pays (on CPU interpret mode
    that includes execution).  :func:`repro.obs.profile.kernel_timer`
    layers the metrics/trace recording on top.  Zero cost uninstalled:
    one ``None`` check per dispatch."""
    global _DISPATCH_HOOK
    prev = _DISPATCH_HOOK
    _DISPATCH_HOOK = fn
    try:
        yield
    finally:
        _DISPATCH_HOOK = prev


def _dispatch(kind: str, fn, *args):
    if _DISPATCH_HOOK is None:
        return fn(*args)
    t0 = time.perf_counter()
    out = fn(*args)
    _DISPATCH_HOOK(kind, time.perf_counter() - t0)
    return out


# ---------------------------------------------------------------------------
# Jitted-wrapper cache (per-op: repeated layers share one compiled kernel)
# ---------------------------------------------------------------------------

_JIT_CACHE: dict[tuple, object] = {}
_JIT_STATS = {"hits": 0, "misses": 0}


def _jitted(kind: str, builder, *static) -> object:
    """The jitted kernel wrapper for ``(kind, *static)``, built once.

    ``builder`` receives the static args and returns the function to jit.
    jax.jit's own signature cache then handles per-shape retraces, so a
    model whose layers share a kernel configuration compiles it once."""
    key = (kind,) + static
    fn = _JIT_CACHE.get(key)
    if fn is None:
        _JIT_STATS["misses"] += 1
        fn = _JIT_CACHE[key] = jax.jit(builder(*static))
    else:
        _JIT_STATS["hits"] += 1
    return fn


def kernel_cache_stats() -> dict[str, int]:
    """Hit/miss counters of the jitted-wrapper cache (plus its size)."""
    return dict(_JIT_STATS, entries=len(_JIT_CACHE))


def clear_kernel_cache() -> None:
    _JIT_CACHE.clear()
    _JIT_STATS["hits"] = _JIT_STATS["misses"] = 0


def wrapper_keys() -> list[tuple]:
    """Keys of the cached wrappers: ``(kind, *static)``, whose last static
    knob is ``interpret`` — so a run can show that every kernel it built
    went through Mosaic."""
    return list(_JIT_CACHE)


def _row_tile(x: jax.Array, bm: int | None) -> int:
    """The row tile a call runs: ``bm`` (default: :func:`tiling.row_tile`)
    rounded up to the activation dtype's sublane multiple, and no taller
    than the padded activation."""
    m = x.shape[0]
    if bm is None:
        return tiling.row_tile(m, x.dtype.itemsize)
    sub = tiling.sublane(x.dtype.itemsize)
    return -(-min(bm, max(m, 1)) // sub) * sub


def _padded_rows(kernel, bm: int):
    """``kernel(x, *operands)`` over ``x`` zero-padded to a multiple of
    ``bm`` rows, with the padding sliced off the (M, K) output."""
    def call(x, *operands):
        m = x.shape[0]
        pad = -m % bm
        if pad:
            x = jnp.pad(x, ((0, pad), (0, 0)))
        return kernel(x, *operands)[:m]
    return call


# ---------------------------------------------------------------------------
# Bitmap block-sparse
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class BitmapCompressed:
    """`B(N₁)-B(K₁)-None(N₂,K₂)` weights: payload + pre-decoded metadata."""

    blocks: jax.Array          # (nnzb, bn, bk)
    counts: jax.Array          # (K/bk,) int32
    row_ids: jax.Array         # (nnzb,) int32
    offsets: jax.Array         # (K/bk,) int32
    n: int
    k: int
    bn: int
    bk: int
    max_per_col: int

    @property
    def compression_ratio(self) -> float:
        dense = self.n * self.k
        stored = self.blocks.shape[0] * self.bn * self.bk
        meta = (self.n // self.bn) * (self.k // self.bk) / 8 / 2  # bits→bytes/2B
        return (stored + meta) / dense


def compress_bitmap(w, bn: int = 128, bk: int = 128) -> BitmapCompressed:
    blocks, counts, row_ids, offsets, bitmap = ref.compress_bitmap_host(
        np.asarray(w), bn, bk)
    return BitmapCompressed(
        blocks=jnp.asarray(blocks), counts=jnp.asarray(counts),
        row_ids=jnp.asarray(row_ids), offsets=jnp.asarray(offsets),
        n=w.shape[0], k=w.shape[1], bn=bn, bk=bk,
        max_per_col=int(counts.max()) if counts.size else 1)


def _bitmap_builder(k: int, bm: int, t_max: int, pipeline: bool,
                    interpret: bool):
    return _padded_rows(
        functools.partial(bitmap_spmm_pallas, k=k, bm=bm, t_max=t_max,
                          pipeline=pipeline, interpret=interpret), bm)


def bitmap_spmm(x: jax.Array, w: BitmapCompressed, bm: int | None = None,
                t_max: int | None = None,
                pipeline: bool | None = None) -> jax.Array:
    """Y = X @ W_blocksparse; dispatches to the Pallas kernel.

    ``bm`` is the row tile (default :func:`tiling.row_tile` of the row
    count); rows pad to a multiple of it inside the call.

    ``t_max`` (default: ``w.max_per_col``) is part of the static cache key,
    so the naive path's innermost grid bound is always the statically-known
    tightest — even under jit/scan, where ``counts`` is a tracer and the
    kernel's own inference would have to assume every stored block.  A
    layer-stacked store passes its shared across-layers bound here, which
    is what keys the cache on the STACKED configuration instead of
    per-layer values (and what lets scanned and unrolled forwards share
    entries — see the module docstring).  The streaming path ignores
    ``t_max`` (its loop bound is the runtime ``counts[kj]``) but keeps it
    in the key so switching paths never aliases a wrapper."""
    _fault_check("bitmap")
    if t_max is None:
        t_max = w.max_per_col
    fn = _jitted("bitmap", _bitmap_builder, w.k, _row_tile(x, bm),
                 max(int(t_max), 1), resolve_pipeline(pipeline), _interpret())
    return _dispatch("bitmap", fn, x, w.blocks, w.counts, w.row_ids,
                     w.offsets)


# ---------------------------------------------------------------------------
# N:M structured
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class NMCompressed:
    values: jax.Array          # (N·n/m, K)
    indices: jax.Array         # (N·n/m, K) int8 ∈ [0, m)
    n: int
    k: int
    n_sel: int = 2
    m_group: int = 4

    @property
    def compression_ratio(self) -> float:
        # values halve; 2-bit indices ≈ n_sel/m_group · 2/16 of dense bits
        return self.n_sel / self.m_group * (1 + 2 / 16)


def compress_nm(w, n_sel: int = 2, m_group: int = 4) -> NMCompressed:
    vals, idx = ref.compress_nm_host(np.asarray(w), n_sel, m_group)
    return NMCompressed(values=jnp.asarray(vals), indices=jnp.asarray(idx),
                        n=w.shape[0], k=w.shape[1],
                        n_sel=n_sel, m_group=m_group)


def _nm_builder(n_sel: int, m_group: int, bm: int, bn: int, bk: int,
                pipeline: bool, interpret: bool):
    return _padded_rows(
        functools.partial(nm_spmm_pallas, n_sel=n_sel, m_group=m_group,
                          bm=bm, bn=bn, bk=bk, pipeline=pipeline,
                          interpret=interpret), bm)


def nm_spmm(x: jax.Array, w: NMCompressed, bm: int | None = None,
            bn: int | None = None, bk: int | None = None,
            pipeline: bool | None = None) -> jax.Array:
    """Y = X @ W_nm; dispatches to the Pallas kernel.  ``bm`` as in
    :func:`bitmap_spmm`; ``bn`` / ``bk`` default to the widest
    lane-aligned tiles of at most 128 (:func:`tiling.lane_tile`)."""
    _fault_check("nm")
    bn = tiling.lane_tile(w.n) if bn is None else bn
    bk = tiling.lane_tile(w.k) if bk is None else bk
    fn = _jitted("nm", _nm_builder, w.n_sel, w.m_group, _row_tile(x, bm),
                 bn, bk, resolve_pipeline(pipeline), _interpret())
    return _dispatch("nm", fn, x, w.values, w.indices)


# ---------------------------------------------------------------------------
# Flash attention
# ---------------------------------------------------------------------------

def _flash_builder(causal: bool, bq: int, bk: int, interpret: bool):
    from repro.kernels.flash_attention import flash_attention_pallas
    return functools.partial(flash_attention_pallas, causal=causal,
                             bq=bq, bk=bk, interpret=interpret)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, bq: int = 128, bk: int = 128
                    ) -> jax.Array:
    fn = _jitted("flash", _flash_builder, causal, bq, bk, _interpret())
    return _dispatch("flash", fn, q, k, v)
