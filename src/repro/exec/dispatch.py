"""Plan-driven matmul dispatch: compressed kernels inside the real model.

The transformer's FFN/attention projections all route through
:func:`repro.models.layers.proj`.  :class:`CompressedModel` installs a hook
there and drives the model's OWN scanned layer stack with an ``extras``
pytree — the :class:`~repro.exec.compress.StackedStore`'s layer-stacked
compressed payloads ride ``lax.scan``'s xs, the scan body publishes each
layer's slice through :func:`repro.models.layers.layer_ctx`, and the hook
resolves it into the matching Pallas kernel call (``bitmap_spmm`` /
``nm_spmm``, interpret mode on CPU, native on TPU).  Dense-kind roles fall
through to the exact einsum the dense model runs.  Because the compiled
graph is the dense model's one scanned block (HLO O(1) in depth) and padded
payload blocks sit beyond every column's ``counts``, compressed and dense
forwards differ only by kernel accumulation order — and the scanned and
unrolled compressed forwards are bit-identical.

The per-layer Python re-drive from the previous revision survives as
:meth:`CompressedModel.hidden_states_unrolled` (equivalence tests, the
scan-vs-unrolled benchmark).  Kernel wrappers are jit-cached per static
configuration (:func:`repro.kernels.ops` ``_jitted``); the stacked path
keys that cache on the SHARED across-layers configuration per role, so a
whole serving trace costs ``len(plan.ops)`` kernel builds, not
``n_layers ×`` that.

Counter semantics under jit/scan (:func:`instrument`): the hook runs at
TRACE time, once per (role) per traced scan body — so one scanned forward
records each role ONCE, with totals covering all ``n_layers`` layers
(``calls += n_layers``, bits/MACs/decode-ops summed over the layer axis
from host-side stacked accounting).  Per-layer means (``calls``,
``w_fetch_bits_per_call``) therefore match the unrolled per-layer loop
exactly, and :mod:`repro.exec.calibrate` fits the same coefficients on
either path.  Re-running a jitted function does NOT re-record (no retrace);
wrap the traced call in a fresh ``instrument()`` block per measurement.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import PartitionSpec as P

from repro.exec.compress import (CompressedStore, CompressedTensor,
                                 StackedStore, stack_store)
from repro.kernels import ops as kops
from repro.kernels import tiling
from repro.models import layers as L
from repro.models import sharding
from repro.models import transformer as T


# ---------------------------------------------------------------------------
# Measured traffic counters
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class OpCounters:
    """Accumulated measured traffic of one dispatch role."""

    calls: int = 0
    w_fetch_bits: float = 0.0     # payload + metadata, realized encoding
    x_bits: float = 0.0
    y_bits: float = 0.0
    macs: float = 0.0             # useful MACs (compressed operand elems × M)
    decode_ops: float = 0.0       # metadata units decoded (blocks / indices)
    # distinct-vs-total streaming split (the memory pipeline's two levels):
    # DISTINCT bits cross DRAM→chip once per call; STREAM bits count every
    # HBM→VMEM payload transfer the kernel grid actually issues — one full
    # pass per output-row stripe (M / tile_M), so stream/distinct is the
    # realized refetch factor the cost model's reuse term prices.
    w_distinct_bits: float = 0.0
    w_stream_bits: float = 0.0

    @property
    def w_fetch_bits_per_call(self) -> float:
        return self.w_fetch_bits / self.calls if self.calls else 0.0

    @property
    def w_stream_bits_per_call(self) -> float:
        return self.w_stream_bits / self.calls if self.calls else 0.0

    @property
    def refetch_factor(self) -> float:
        """Measured total-stream / distinct-fetch ratio (≥ 1)."""
        if not self.w_distinct_bits:
            return 1.0
        return self.w_stream_bits / self.w_distinct_bits


_ACTIVE_COUNTERS: Optional[dict[str, OpCounters]] = None


@contextlib.contextmanager
def instrument() -> Iterator[dict[str, OpCounters]]:
    """Collect per-role :class:`OpCounters` for every dispatched projection
    TRACED inside the context (nested dispatchers share the dict).

    Tracing is the recording event: the unrolled path records once per
    (layer, role); the scanned path records once per role with
    ``calls += n_layers`` and layer-summed totals, so per-call means agree.
    A jit cache hit replays without recording — time a jitted forward by
    tracing it inside the block (or clearing jax's cache first)."""
    global _ACTIVE_COUNTERS
    prev = _ACTIVE_COUNTERS
    counters: dict[str, OpCounters] = {}
    _ACTIVE_COUNTERS = counters
    try:
        yield counters
    finally:
        _ACTIVE_COUNTERS = prev


def _record(role: str, x2: jax.Array, y_k: int,
            w_bits: float, macs: float, decode_ops: float,
            layers: int = 1, stream_passes: int = 1) -> None:
    """Record one dispatch covering ``layers`` realized layer matmuls.

    ``w_bits``/``macs``/``decode_ops`` are totals over those layers; x/y
    activation traffic is per-layer and scaled here.  ``stream_passes`` is
    how many times the kernel grid re-streams the full weight payload
    (one pass per output-row stripe, M / tile_M)."""
    if _ACTIVE_COUNTERS is None:
        return
    c = _ACTIVE_COUNTERS.setdefault(role, OpCounters())
    c.calls += layers
    c.w_fetch_bits += w_bits
    c.x_bits += float(layers * x2.size * x2.dtype.itemsize * 8)
    c.y_bits += float(layers * x2.shape[0] * y_k * 32)   # kernels emit f32
    c.macs += macs
    c.decode_ops += decode_ops
    c.w_distinct_bits += w_bits
    c.w_stream_bits += w_bits * stream_passes


def measured_w_bits(entry: CompressedTensor) -> float:
    """Realized W-side bits one full pass over ``entry`` fetches."""
    return entry.stored_bits


# ---------------------------------------------------------------------------
# Trace-time kernel-failure guard
# ---------------------------------------------------------------------------

_KERNEL_GUARD = None


@contextlib.contextmanager
def kernel_guard(sink) -> Iterator[None]:
    """Per-role dense fallback for kernel dispatch failures.

    While active, an exception raised by a compressed kernel call inside a
    dispatcher (a lowering/launch failure — or an injected one, see
    :func:`repro.kernels.ops.kernel_fault_hook`) is reported to
    ``sink(role, exc)`` and the projection returns ``None``, falling
    through to the dense einsum over the params pytree, instead of failing
    the whole forward.  The failure surfaces at TRACE time, so the demotion
    is baked into that trace's compiled graph.  Without the guard (the
    default) kernel exceptions propagate unchanged."""
    global _KERNEL_GUARD
    prev = _KERNEL_GUARD
    _KERNEL_GUARD = sink
    try:
        yield
    finally:
        _KERNEL_GUARD = prev


def _guarded_kernel(role: str, fn):
    """Run one kernel dispatch under the active guard (if any)."""
    if _KERNEL_GUARD is None:
        return fn()
    try:
        return fn()
    except Exception as e:                     # noqa: BLE001 — reported, not hidden
        _KERNEL_GUARD(role, e)
        return None


# ---------------------------------------------------------------------------
# The dispatchers (repro.models.layers.proj hooks)
# ---------------------------------------------------------------------------

def _row_passes(x2: jax.Array) -> int:
    """Output-row stripes the kernel grid streams the weights for: one per
    row tile the wrappers pick (:func:`repro.kernels.tiling.row_tile`)."""
    m = x2.shape[0]
    return -(-m // tiling.row_tile(m, x2.dtype.itemsize))


def _on_rows(kernel, x2: jax.Array, operands: dict) -> jax.Array:
    """``kernel(x2, operands)``; under a bound serving mesh, once per data
    shard of the rows with the operands replicated.

    XLA cannot partition a Mosaic custom call, so the batch-sharded
    activations of ``generate(mesh=...)`` reach the kernel through
    ``shard_map``: each device runs the kernel on its own rows."""
    mesh = sharding.bound_mesh()
    if mesh is None:
        return kernel(x2, operands)
    rows = sharding.spec("batch", None)
    return jax.shard_map(kernel, mesh=mesh, in_specs=(rows, P()),
                         out_specs=rows, check_vma=False)(x2, operands)


class _Dispatcher:
    """Per-(layer, role) hook for the UNROLLED reference forward (expert
    roles fall through to the dense einsum).

    Bitmap kernels use one per-role ``t_max`` (max over layers), so every
    layer of a role shares a single jitted kernel configuration — same
    cache-sharing property the stacked path gets by construction."""

    def __init__(self, store: CompressedStore):
        self.store = store
        self.layer = 0
        self._t_max: dict[str, int] = {}
        for e in store:
            if e.kind == "bitmap" and e.expert < 0:
                self._t_max[e.role] = max(self._t_max.get(e.role, 1),
                                          e.data.max_per_col)

    def __call__(self, x: jax.Array, w: jax.Array, role: str
                 ) -> Optional[jax.Array]:
        entry = self.store.get(self.layer, role)
        if entry is None:
            return None                       # unplanned role: dense einsum
        lead = x.shape[:-1]
        x2 = x.reshape(-1, x.shape[-1])
        m = x2.shape[0]
        if entry.kind == "bitmap":
            d = entry.data
            y = _guarded_kernel(role, lambda: kops.bitmap_spmm(
                x2, d, t_max=self._t_max[role]))
            if y is None:                     # guarded kernel failure: dense
                return None
            nnzb = int(np.asarray(d.counts).sum())
            _record(role, x2, d.k, w_bits=entry.stored_bits,
                    macs=float(m) * nnzb * d.bn * d.bk,
                    decode_ops=float(nnzb),
                    stream_passes=_row_passes(x2))
        elif entry.kind == "nm":
            d = entry.data
            y = _guarded_kernel(role, lambda: kops.nm_spmm(x2, d))
            if y is None:                     # guarded kernel failure: dense
                return None
            _record(role, x2, d.k, w_bits=entry.stored_bits,
                    macs=float(m) * d.values.size,
                    decode_ops=float(d.indices.size),
                    stream_passes=_row_passes(x2))
        else:
            # dense-kind: record the dense traffic, run the standard einsum
            _record(role, x2, w.shape[-1],
                    w_bits=entry.stored_bits,
                    macs=float(m) * w.size, decode_ops=0.0)
            return None
        return y.astype(x.dtype).reshape(*lead, y.shape[-1])


class _StackedDispatcher:
    """Hook for the SCANNED forward: static kernel configuration from the
    :class:`StackedStore`, per-layer operands from the scan body's
    ``layer_ctx`` slice.  Runs once per role per trace; the compiled scan
    replays it for every layer with that layer's payload slice.  A MoE
    role's slice holds every held expert: each is served by its own kernel
    call, over every row (``models/moe.py``)."""

    def __init__(self, stacked: StackedStore):
        self.stacked = stacked

    def __call__(self, x: jax.Array, w: jax.Array, role: str
                 ) -> Optional[jax.Array]:
        sr = self.stacked.roles.get(role)
        if sr is None:
            return None                       # unplanned role: dense einsum
        lead = x.shape[:-1]
        x2 = x.reshape(-1, x.shape[-1])
        m = x2.shape[0]
        nl = self.stacked.n_layers
        calls = nl * max(sr.experts, 1)
        if sr.kind == "dense":
            _record(role, x2, w.shape[-1], w_bits=sr.stored_bits,
                    macs=float(m) * sr.payload_elems, decode_ops=0.0,
                    layers=calls)
            return None
        e = L.current_layer_ctx()
        if e is None or role not in e:
            return None       # hook active outside a carrying scan: dense
        if sr.kind == "bitmap":
            def kernel(xs, d):
                return kops.bitmap_spmm(xs, kops.BitmapCompressed(
                    blocks=d["blocks"], counts=d["counts"],
                    row_ids=d["row_ids"], offsets=d["offsets"],
                    n=sr.n, k=sr.k, bn=sr.bn, bk=sr.bk,
                    max_per_col=sr.t_max), t_max=sr.t_max)
        else:                                 # nm
            def kernel(xs, d):
                return kops.nm_spmm(xs, kops.NMCompressed(
                    values=d["values"], indices=d["indices"],
                    n=sr.n, k=sr.k, n_sel=sr.n_sel, m_group=sr.m_group))
        if sr.experts:
            # x (T, n) shared by the experts, or (E, T, n) one row each
            xs = [x if x.ndim == 2 else x[j] for j in range(sr.experts)]
            y = _guarded_kernel(role, lambda: jnp.stack([_on_rows(
                kernel, xs[j], {name: a[j] for name, a in e[role].items()})
                for j in range(sr.experts)]))
            x2, lead = xs[0], (sr.experts,) + xs[0].shape[:-1]
        else:
            y = _guarded_kernel(role, lambda: _on_rows(kernel, x2, e[role]))
        if y is None:                         # guarded kernel failure: dense
            return None
        _record(role, x2, sr.k, w_bits=sr.stored_bits,
                macs=float(x2.shape[0]) * sr.payload_elems,
                decode_ops=sr.decode_units, layers=calls,
                stream_passes=_row_passes(x2))
        return y.astype(x.dtype).reshape(*lead, y.shape[-1])


def jit_serving(model, fn, **jit_kwargs):
    """``jax.jit(fn, **jit_kwargs)`` for one of ``model``'s serving methods
    (``prefill`` / ``decode_step``, possibly partially applied).  For a
    :class:`CompressedModel` every call passes its layer-stacked payloads
    as the ``extras`` argument, so they reach the compiled program as
    arguments and not as constants; a model without ``extras`` (the dense
    :class:`~repro.models.transformer.Model`) gets the plain jit."""
    jitted = jax.jit(fn, **jit_kwargs)
    extras = getattr(model, "extras", None)
    return jitted if extras is None else functools.partial(jitted,
                                                           extras=extras)


@contextlib.contextmanager
def active(store: CompressedStore) -> Iterator[_Dispatcher]:
    """Install the per-layer dispatch hook for ``store`` (unrolled path)."""
    disp = _Dispatcher(store)
    L.set_proj_hook(disp)
    try:
        yield disp
    finally:
        L.set_proj_hook(None)


@contextlib.contextmanager
def active_stacked(stacked: StackedStore) -> Iterator[_StackedDispatcher]:
    """Install the scan-carried dispatch hook for ``stacked``."""
    disp = _StackedDispatcher(stacked)
    L.set_proj_hook(disp)
    try:
        yield disp
    finally:
        L.set_proj_hook(None)


# ---------------------------------------------------------------------------
# Compressed forward / serving surface
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class CompressedModel:
    """A served model: dense params for the un-planned pieces + a
    :class:`CompressedStore` for every planned projection.

    Mirrors :class:`repro.models.transformer.Model`'s serving surface
    (``prefill`` / ``init_cache`` / ``decode_step`` / ``hidden_states``)
    for uniform attention stacks, driving the model's OWN scanned bodies
    with the layer-stacked store as scan extras; each held expert's
    projections are served by its own kernel call."""

    model: T.Model
    store: CompressedStore
    stacked: Optional[StackedStore] = None

    def __post_init__(self):
        if self.stacked is None:
            self.stacked = stack_store(self.store)

    @property
    def cfg(self):
        return self.model.cfg

    @property
    def extras(self) -> dict:
        """The layer-stacked payloads the scanned forward reads.  Serving
        drivers pass them to their jitted steps as an argument
        (:func:`jit_serving`); a jitted step that closed over them would
        carry them as constants of its compiled program — 1.6 GB per
        program for 4 chatglm3-6b layers."""
        return self.stacked.extras()

    # -- integrity ----------------------------------------------------------
    def verify(self) -> dict[str, str]:
        """Verify BOTH representations this model serves from: the
        per-layer store (checksums + structure) and the layer-stacked
        serving payloads.  Raises the first
        :class:`repro.runtime.integrity.IntegrityError`; returns the merged
        ``{role: "ok"}`` map otherwise."""
        out = self.store.verify()
        out.update(self.stacked.verify())
        return out

    def demoted(self, roles) -> "CompressedModel":
        """A new model with the given roles served DENSE (entries dropped
        from the store; the stacked representation is rebuilt).  The guarded
        serving path calls this after an integrity violation so one corrupt
        role costs its compression ratio, not the whole batch."""
        return CompressedModel(self.model, self.store.without_roles(roles))

    # -- full-sequence forward ---------------------------------------------
    def hidden_states(self, params, tokens: jax.Array) -> jax.Array:
        with active_stacked(self.stacked):
            return self.model.hidden_states(params, tokens, remat=False,
                                            extras=self.extras)

    def hidden_states_unrolled(self, params, tokens: jax.Array) -> jax.Array:
        """Previous-revision reference: per-layer Python loop re-driving the
        layer body (O(layers) HLO).  Kept for scanned-vs-unrolled
        equivalence tests and the bench_serve comparison row."""
        cfg = self.model.cfg
        b, s = tokens.shape
        x = L.embed(tokens, params["embed"])
        positions = jnp.arange(s)
        freqs = L.rope_freqs(cfg)
        layer_xs = L.layer_attn_xs(cfg)
        with active(self.store) as disp:
            for layer in range(cfg.n_layers):
                disp.layer = layer
                p = jax.tree.map(lambda a: a[layer], params["blocks"])
                lx = None if layer_xs is None else \
                    jax.tree.map(lambda a: a[layer], layer_xs)
                fr, scale, win = T._layer_attn(lx, freqs, cfg.window)
                x = T._attn_layer(x, p, cfg, fr, positions, causal=True,
                                  window=win, rope_scale=scale)
        return L.rms_norm(x, params["final_norm"], cfg.norm_eps)

    def logits(self, params, tokens: jax.Array) -> jax.Array:
        x = self.hidden_states(params, tokens)
        return jnp.einsum("btd,vd->btv", x,
                          L.head_table(params).astype(L.COMPUTE_DTYPE))

    # -- serving (prefill + KV-cache decode) --------------------------------
    def prefill(self, params, tokens: jax.Array, max_len: int,
                extras=None):
        """Compressed full-sequence forward that fills a decode cache —
        same contract as :meth:`repro.models.transformer.Model.prefill`.
        ``extras`` defaults to :attr:`extras`."""
        with active_stacked(self.stacked):
            return self.model.prefill(
                params, tokens, max_len,
                extras=self.extras if extras is None else extras)

    def init_cache(self, batch: int, max_len: int):
        return self.model.init_cache(batch, max_len)

    def decode_step(self, params, cache, tokens: jax.Array, pos: jax.Array,
                    extras=None):
        """One compressed decode token for the whole batch — same contract
        as :meth:`repro.models.transformer.Model.decode_step`.  ``pos`` may
        be a scalar (lockstep batch) or a per-slot ``(B,)`` vector (the
        continuous-batching mixer / ragged-prompt serving).  ``extras``
        defaults to :attr:`extras`."""
        with active_stacked(self.stacked):
            return self.model.decode_step(
                params, cache, tokens, pos,
                extras=self.extras if extras is None else extras)

    def generate(self, params, prompts: jax.Array, gen: int,
                 max_len: Optional[int] = None, **kwargs):
        """Greedy batched generation (shared driver with the dense model:
        :func:`repro.launch.serve.generate`).  Returns
        (tokens (B, gen), t_prefill_s, t_gen_s); with ``guarded=True`` a
        :class:`repro.runtime.guard.HealthReport` is appended."""
        from repro.launch import serve
        if max_len is None:
            max_len = prompts.shape[1] + gen
        return serve.generate(self, params, prompts, gen, max_len, **kwargs)

    def serve_mixed(self, params, requests, *, slots: int,
                    max_len: int, **kwargs):
        """Continuous-batching serve of a request STREAM over the
        compressed plane (delegates to :class:`repro.launch.mixer.Mixer`,
        same as :meth:`generate` delegates to the static driver).  Returns
        ``(results, mixer)`` — per-request :class:`RequestResult`\\ s in
        request order plus the drained mixer (events / stats)."""
        from repro.launch.mixer import Mixer
        mx = Mixer(self, params, slots=slots, max_len=max_len, **kwargs)
        return mx.run(requests), mx
