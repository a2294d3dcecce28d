"""Payload integrity for the compressed serving plane.

A compressed store is a single point of silent corruption: one flipped
bitmap count or truncated N:M index buffer produces garbage tokens, not a
crash.  This module makes corruption LOUD, in two layers:

  * **Content checksums** — :func:`checksum_store` digests every role's
    compressed payload (sha256 over the *logical* encoding: counts,
    offsets, the first ``nnzb`` row ids and blocks — so the per-layer
    store and the padded layer-stacked store hash identically).
    ``compress.compress_params`` records the digests in the plan
    (``ExecPlan.checksums``, JSON round-tripped); ``CompressedStore.verify``
    / ``StackedStore.verify`` recompute and compare.
  * **Structural invariants** — cheap shape/range checks that need no
    reference digest: per-column counts non-negative and ≤ the block-grid
    rows, offsets exactly the exclusive cumsum of counts (hence monotone),
    row ids inside the grid, payload within capacity, N:M indices inside
    ``[0, m_group)``.  These run even for plans that predate checksums.

Violations raise a structured :class:`IntegrityError` carrying
``(layer, role, reason)`` so the guarded serving path
(:mod:`repro.runtime.guard`) can demote exactly the failing role to dense
weights instead of serving garbage — or crashing the whole batch.

Everything here is duck-typed over the store/stacked dataclasses (no
import of :mod:`repro.exec` — the exec plane imports *us* lazily).
"""

from __future__ import annotations

import hashlib
from typing import Iterator, Optional

import numpy as np


class IntegrityError(RuntimeError):
    """A compressed payload failed verification.

    Structured: ``role`` and ``reason`` are always set; ``layer`` is the
    first offending layer when known (``None`` for role-wide digest
    mismatches where the layer cannot be localized)."""

    def __init__(self, role: str, reason: str,
                 layer: Optional[int] = None, detail: str = ""):
        self.role = role
        self.reason = reason
        self.layer = layer
        self.detail = detail
        where = f"layer {layer} " if layer is not None else ""
        msg = f"integrity violation at {where}role {role!r}: {reason}"
        if detail:
            msg += f" ({detail})"
        super().__init__(msg)


# ---------------------------------------------------------------------------
# Structural invariants
# ---------------------------------------------------------------------------

def check_bitmap_structure(role: str, layer: int, counts, offsets, row_ids,
                           blocks, n: int, k: int, bn: int, bk: int) -> None:
    """Invariants of one layer's bitmap CSC encoding (cheap, O(grid))."""
    gn, gk = n // bn, k // bk
    counts = np.asarray(counts)
    offsets = np.asarray(offsets)
    if counts.shape != (gk,) or offsets.shape != (gk,):
        raise IntegrityError(role, "metadata_shape_mismatch", layer,
                             f"counts {counts.shape} offsets {offsets.shape} "
                             f"for grid ({gn},{gk})")
    if counts.size and int(counts.min()) < 0:
        raise IntegrityError(role, "negative_count", layer)
    if counts.size and int(counts.max()) > gn:
        raise IntegrityError(role, "count_exceeds_blocks", layer,
                             f"max count {int(counts.max())} > {gn} "
                             f"block rows per column")
    nnzb = int(counts.sum())
    capacity = int(np.asarray(blocks).shape[0])
    if nnzb > capacity:
        raise IntegrityError(role, "payload_overflow", layer,
                             f"counts sum to {nnzb} blocks but payload "
                             f"holds {capacity}")
    expect = np.concatenate([[0], np.cumsum(counts[:-1])]).astype(np.int64) \
        if counts.size else np.zeros(0, np.int64)
    if not np.array_equal(offsets.astype(np.int64), expect):
        raise IntegrityError(role, "offsets_not_cumsum", layer,
                             "offsets are not the exclusive cumsum of "
                             "counts (truncated or non-monotone)")
    rid = np.asarray(row_ids)[:nnzb]
    if rid.size and (int(rid.min()) < 0 or int(rid.max()) >= gn):
        raise IntegrityError(role, "row_id_out_of_range", layer,
                             f"row ids must lie in [0, {gn})")


def check_nm_structure(role: str, layer: int, values, indices,
                       n: int, k: int, n_sel: int, m_group: int) -> None:
    """Invariants of one layer's N:M encoding."""
    values = np.asarray(values)
    indices = np.asarray(indices)
    expect = (n * n_sel // m_group, k)
    if values.shape != expect or indices.shape != expect:
        raise IntegrityError(role, "payload_shape_mismatch", layer,
                             f"values {values.shape} indices {indices.shape} "
                             f"expected {expect}")
    if indices.size and (int(indices.min()) < 0
                         or int(indices.max()) >= m_group):
        raise IntegrityError(role, "nm_index_out_of_range", layer,
                             f"indices must lie in [0, {m_group})")


# ---------------------------------------------------------------------------
# Content checksums
# ---------------------------------------------------------------------------

def _digest_bitmap(h, layer: int, expert: int, counts, offsets, row_ids,
                   blocks, n: int, k: int, bn: int, bk: int) -> None:
    counts = np.asarray(counts)
    nnzb = int(counts.sum())
    blocks = np.asarray(blocks)
    h.update(f"bitmap:{layer}:{expert}:{n}x{k}/{bn}x{bk}:"
             f"{blocks.dtype.str}".encode())
    h.update(np.ascontiguousarray(counts, np.int64).tobytes())
    h.update(np.ascontiguousarray(np.asarray(offsets), np.int64).tobytes())
    h.update(np.ascontiguousarray(np.asarray(row_ids)[:nnzb],
                                  np.int64).tobytes())
    h.update(np.ascontiguousarray(blocks[:nnzb]).tobytes())


def _digest_nm(h, layer: int, expert: int, values, indices,
               n: int, k: int, n_sel: int, m_group: int) -> None:
    values = np.asarray(values)
    h.update(f"nm:{layer}:{expert}:{n}x{k}:{n_sel}:{m_group}:"
             f"{values.dtype.str}".encode())
    h.update(np.ascontiguousarray(values).tobytes())
    h.update(np.ascontiguousarray(np.asarray(indices),
                                  np.int64).tobytes())


def _digest_dense(h, layer: int, expert: int, w) -> None:
    w = np.asarray(w)
    h.update(f"dense:{layer}:{expert}:{w.shape}:{w.dtype.str}".encode())
    h.update(np.ascontiguousarray(w).tobytes())


def _digest_entry(h, e) -> None:
    d = e.data
    if e.kind == "bitmap":
        _digest_bitmap(h, e.layer, e.expert, d.counts, d.offsets, d.row_ids,
                       d.blocks, d.n, d.k, d.bn, d.bk)
    elif e.kind == "nm":
        _digest_nm(h, e.layer, e.expert, d.values, d.indices,
                   d.n, d.k, d.n_sel, d.m_group)
    else:
        _digest_dense(h, e.layer, e.expert, d)


def checksum_store(store) -> dict[str, str]:
    """Per-role sha256 hexdigests of a :class:`CompressedStore`'s payloads.

    Entries of a role digest in (layer, expert) order.  The digest covers
    only the logical encoding (``[:nnzb]`` slices for bitmap), so the
    padded :class:`StackedStore` representation reproduces it exactly."""
    by_role: dict[str, list] = {}
    for e in store:
        by_role.setdefault(e.role, []).append(e)
    out: dict[str, str] = {}
    for role in sorted(by_role):
        h = hashlib.sha256()
        for e in sorted(by_role[role], key=lambda e: (e.layer, e.expert)):
            _digest_entry(h, e)
        out[role] = h.hexdigest()
    return out


# ---------------------------------------------------------------------------
# Verification drivers
# ---------------------------------------------------------------------------

def _store_role_errors(store) -> Iterator[tuple[str, Optional[IntegrityError]]]:
    """(role, first violation or None) for every role of a per-layer store.

    Structure is checked first (entry by entry), then the content digest is
    compared against ``store.plan.checksums`` — a plan without recorded
    checksums (pre-PR-8, or synthetic) gets structure-only verification."""
    recorded = dict(getattr(store.plan, "checksums", None) or {})
    by_role: dict[str, list] = {}
    for e in store:
        by_role.setdefault(e.role, []).append(e)
    for role in sorted(by_role):
        entries = sorted(by_role[role], key=lambda e: (e.layer, e.expert))
        err: Optional[IntegrityError] = None
        try:
            for e in entries:
                d = e.data
                if e.kind == "bitmap":
                    check_bitmap_structure(role, e.layer, d.counts, d.offsets,
                                           d.row_ids, d.blocks,
                                           d.n, d.k, d.bn, d.bk)
                elif e.kind == "nm":
                    check_nm_structure(role, e.layer, d.values, d.indices,
                                       d.n, d.k, d.n_sel, d.m_group)
            if role in recorded:
                h = hashlib.sha256()
                for e in entries:
                    _digest_entry(h, e)
                if h.hexdigest() != recorded[role]:
                    err = IntegrityError(role, "checksum_mismatch",
                                         detail="payload bytes differ from "
                                                "the digest recorded at "
                                                "compress time")
        except IntegrityError as e:
            err = e
        yield role, err


def _stacked_role_errors(stacked
                         ) -> Iterator[tuple[str, Optional[IntegrityError]]]:
    """(role, first violation or None) for a layer-stacked store.

    Dense-kind roles carry no stacked payload (they ride in the params
    pytree) and are skipped; kernel-backed roles re-derive each layer's
    (and each held expert's) logical encoding from the padded slices, so
    the recorded per-entry digests still apply."""
    recorded = dict(getattr(stacked.plan, "checksums", None) or {})
    for role in sorted(stacked.roles):
        sr = stacked.roles[role]
        if sr.data is None:
            continue
        err: Optional[IntegrityError] = None
        experts = range(sr.experts) if sr.experts else (-1,)
        try:
            h = hashlib.sha256()
            for layer in range(stacked.n_layers):
                for expert in experts:
                    at = (layer,) if expert < 0 else (layer, expert)
                    d = {k: np.asarray(v[at]) for k, v in sr.data.items()}
                    if sr.kind == "bitmap":
                        check_bitmap_structure(
                            role, layer, d["counts"], d["offsets"],
                            d["row_ids"], d["blocks"], sr.n, sr.k, sr.bn,
                            sr.bk)
                        _digest_bitmap(h, layer, expert, d["counts"],
                                       d["offsets"], d["row_ids"],
                                       d["blocks"], sr.n, sr.k, sr.bn, sr.bk)
                    else:
                        check_nm_structure(role, layer, d["values"],
                                           d["indices"], sr.n, sr.k,
                                           sr.n_sel, sr.m_group)
                        _digest_nm(h, layer, expert, d["values"],
                                   d["indices"], sr.n, sr.k, sr.n_sel,
                                   sr.m_group)
            if role in recorded and h.hexdigest() != recorded[role]:
                err = IntegrityError(role, "checksum_mismatch",
                                     detail="stacked payload bytes differ "
                                            "from the digest recorded at "
                                            "compress time")
        except IntegrityError as e:
            err = e
        yield role, err


def role_errors(store_or_stacked
                ) -> Iterator[tuple[str, Optional[IntegrityError]]]:
    """Dispatch on store flavor: per-layer stores have ``entries``."""
    if hasattr(store_or_stacked, "entries"):
        return _store_role_errors(store_or_stacked)
    return _stacked_role_errors(store_or_stacked)


def verify(store_or_stacked) -> dict[str, str]:
    """Verify every role; raise the first :class:`IntegrityError`.

    Returns ``{role: "ok"}`` on success (roles a stacked store cannot
    check — dense-kind — are simply absent)."""
    out: dict[str, str] = {}
    for role, err in role_errors(store_or_stacked):
        if err is not None:
            raise err
        out[role] = "ok"
    return out


def verify_report(store_or_stacked) -> dict[str, str]:
    """Non-raising verify: ``{role: "ok" | reason}`` for every role."""
    return {role: "ok" if err is None else err.reason
            for role, err in role_errors(store_or_stacked)}
