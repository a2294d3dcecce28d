"""Apply an :class:`~repro.exec.plans.ExecPlan` to a real weight pytree.

Walks the model's stacked layer parameters, slices each (layer, role)
projection weight out, and stores it in the plan's chosen representation:
:class:`~repro.kernels.ops.BitmapCompressed`,
:class:`~repro.kernels.ops.NMCompressed`, or the dense array.  Every entry
carries EXACT achieved-size accounting (payload + metadata bits of the
realized weights, not the statistical expectation), which is what the
calibration loop compares the cost model's predictions against.

Compression is lossless for weights that already carry the plan's sparsity
structure (block-sparse for bitmap entries, N:M for nm entries);
:func:`prune_params` produces such weights from a dense pytree.  MoE roles
fan out per held expert (one entry per (layer, role, expert), ``expert``
counting the experts held from ``MoECfg.first_expert``).

:func:`stack_store` re-lays a per-layer store as a **layer-stacked**
:class:`StackedStore`: one pytree per role with a leading layer axis (and
an expert axis after it for MoE roles),
padded so every scanned layer shares ONE kernel configuration per role —
the representation ``jax.lax.scan`` carries through the compiled serving
block (:class:`repro.exec.dispatch.CompressedModel` prefill/decode).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.sparsity import NM
from repro.exec.plans import ExecPlan, OpPlan
from repro.kernels import ops as kops
from repro.sparse import masks


def _role_path(role: str) -> tuple[str, str]:
    """Dispatch role → (sub-tree, leaf) inside one layer's param dict."""
    group, leaf = role.split(".", 1)
    if group == "attn":
        return "attn", leaf
    if group in ("ffn", "moe"):
        return "ffn", leaf
    raise KeyError(f"unknown role {role!r}")


@dataclasses.dataclass
class CompressedTensor:
    """One (layer, role[, expert]) weight in its executable representation."""

    layer: int
    role: str
    expert: int                # -1 for non-MoE roles
    kind: str                  # "bitmap" | "nm" | "dense"
    data: Any                  # BitmapCompressed | NMCompressed | jax.Array
    dense_bits: float
    stored_bits: float

    @property
    def achieved_ratio(self) -> float:
        return self.stored_bits / self.dense_bits


@dataclasses.dataclass
class CompressedStore:
    """The compressed parameter store an :class:`ExecPlan` serves from."""

    plan: ExecPlan
    entries: dict[tuple[int, str, int], CompressedTensor]

    def get(self, layer: int, role: str, expert: int = -1
            ) -> Optional[CompressedTensor]:
        return self.entries.get((layer, role, expert))

    def __iter__(self) -> Iterator[CompressedTensor]:
        return iter(self.entries.values())

    def __len__(self) -> int:
        return len(self.entries)

    # -- achieved-ratio accounting -----------------------------------------
    def achieved_ratio(self, role: Optional[str] = None) -> float:
        """stored/dense bits over the whole store (or one role), exact."""
        es = [e for e in self if role is None or e.role == role]
        dense = sum(e.dense_bits for e in es)
        return sum(e.stored_bits for e in es) / dense if dense else 1.0

    def ratio_report(self) -> dict[str, float]:
        roles = sorted({e.role for e in self})
        out = {r: self.achieved_ratio(r) for r in roles}
        out["total"] = self.achieved_ratio()
        return out

    # -- integrity ----------------------------------------------------------
    def verify(self) -> dict[str, str]:
        """Structural invariants + content checksums for every role.

        Raises :class:`repro.runtime.integrity.IntegrityError` on the first
        violation; returns ``{role: "ok"}`` otherwise.  Checksums compare
        against ``plan.checksums`` (recorded by :func:`compress_params`);
        plans without recorded digests get structure-only verification."""
        from repro.runtime import integrity
        return integrity.verify(self)

    def without_roles(self, roles) -> "CompressedStore":
        """A new store with the given roles' entries removed.

        Dropping a role makes the dispatcher fall through to the dense
        einsum over the (pruned) params pytree — the guarded serving path's
        per-role demotion after an integrity violation."""
        drop = set(roles)
        return CompressedStore(self.plan, {
            k: e for k, e in self.entries.items() if e.role not in drop})


def _stored_bits(kind: str, data: Any, vb: int) -> float:
    """Exact stored size: payload + metadata of the realized encoding."""
    if kind == "bitmap":
        nnzb = int(np.asarray(data.counts).sum())   # true non-zero blocks
        gn, gk = data.n // data.bn, data.k // data.bk
        return float(nnzb * data.bn * data.bk * vb + gn * gk)
    if kind == "nm":
        idx_bits = max(1, math.ceil(math.log2(data.m_group)))
        return float(data.values.size * vb + data.indices.size * idx_bits)
    return float(data.size * vb)


def _layer_weight(params: dict, layer: int, role: str, expert: int
                  ) -> jax.Array:
    group, leaf = _role_path(role)
    w = params["blocks"][group][leaf]
    w = w[layer]
    if expert >= 0:
        w = w[expert]
    return w


def _check_uniform(cfg: ModelConfig) -> None:
    if cfg.hybrid is not None or cfg.family not in ("dense", "moe", "vlm"):
        raise NotImplementedError(
            f"exec plane serves stacks whose layers share parameter shapes "
            f"(windows and RoPE may differ per layer, ``attn_pattern``); "
            f"{cfg.name} is family={cfg.family!r} with a hybrid layer "
            f"pattern {cfg.hybrid!r}")


def _fanout(plan_op: OpPlan, cfg: ModelConfig) -> range:
    if plan_op.role.startswith("moe."):
        assert cfg.moe is not None
        return range(cfg.moe.held)
    return range(-1, 0)          # single entry, expert = -1


def compress_params(params: dict, plan: ExecPlan, cfg: ModelConfig
                    ) -> CompressedStore:
    """Compress every planned (layer, role[, expert]) weight of ``params``.

    ``params`` is a :meth:`repro.models.transformer.Model.init` pytree whose
    weights already carry the plan's sparsity structure (see
    :func:`prune_params`).  Dense-kind entries keep the raw array (the
    dispatcher falls through to the dense einsum)."""
    _check_uniform(cfg)
    sp = plan.sparsity
    n_sel, m_group = (sp.n, sp.m) if isinstance(sp, NM) else (2, 4)
    entries: dict[tuple[int, str, int], CompressedTensor] = {}
    for op in plan.ops:
        ch = op.choice
        for layer in range(cfg.n_layers):
            for expert in _fanout(op, cfg):
                w = _layer_weight(params, layer, op.role, expert)
                vb = w.dtype.itemsize * 8
                dense_bits = float(w.size * vb)
                if ch.kind == "bitmap":
                    data: Any = kops.compress_bitmap(
                        np.asarray(w), ch.block_n, ch.block_k)
                elif ch.kind == "nm":
                    data = kops.compress_nm(np.asarray(w), n_sel, m_group)
                else:
                    data = jnp.asarray(w)
                entries[(layer, op.role, expert)] = CompressedTensor(
                    layer=layer, role=op.role, expert=expert, kind=ch.kind,
                    data=data, dense_bits=dense_bits,
                    stored_bits=_stored_bits(ch.kind, data, vb))
    store = CompressedStore(plan, entries)
    # record per-role content digests IN the plan: the plan is the durable
    # artifact (JSON round-tripped), so a store rebuilt or reloaded later
    # verifies against what compression actually produced
    from repro.runtime import integrity
    store.plan = dataclasses.replace(
        plan, checksums=integrity.checksum_store(store))
    return store


# ---------------------------------------------------------------------------
# Layer-stacked store (the scan-compiled serving representation)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class StackedRole:
    """One dispatch role's compressed weights for ALL layers, stacked.

    ``data`` is a dict of arrays with a leading layer axis (``None`` for
    dense-kind roles, which fall through to the dense einsum carried by the
    params pytree itself).  Bitmap payloads are padded to the max non-zero
    block count across layers, so every scanned layer slice has the same
    shape and runs the same kernel grid (``t_max`` is the shared static
    bound).  A MoE role's arrays carry an expert axis after the layer axis
    (``experts`` of them, padded to one bound across layers and experts).
    Accounting fields are totals over the layer (and expert) axes: the EXACT
    realized encoding (``stored_bits``) stays what the calibration loop
    compares against; ``padded_bits`` is what the padded stacked payload
    actually occupies (the price of shape uniformity)."""

    role: str
    kind: str                  # "bitmap" | "nm" | "dense"
    n: int
    k: int
    data: Optional[dict]       # stacked arrays, leading axis = layer
    # static kernel configuration (shared by every scanned layer)
    bn: int = 0
    bk: int = 0
    t_max: int = 1
    n_sel: int = 0
    m_group: int = 0
    experts: int = 0             # held experts per layer; 0: not a MoE role
    # accounting — totals over all layers
    dense_bits: float = 0.0
    stored_bits: float = 0.0
    padded_bits: float = 0.0
    payload_elems: float = 0.0   # compressed operand elements, one full pass
    decode_units: float = 0.0    # metadata units decoded, one full pass


@dataclasses.dataclass
class StackedStore:
    """A :class:`CompressedStore` re-laid for ``jax.lax.scan``: per-role
    pytrees with a leading layer axis + one static kernel config per role."""

    plan: ExecPlan
    n_layers: int
    roles: dict[str, StackedRole]

    def extras(self) -> dict[str, dict]:
        """The scan-carried xs pytree: role → stacked arrays (kernel-backed
        roles only; dense roles ride in the params pytree)."""
        return {r: sr.data for r, sr in self.roles.items()
                if sr.data is not None}

    def padding_overhead(self) -> float:
        """padded/stored bits over the kernel-backed roles (≥ 1)."""
        stored = sum(sr.stored_bits for sr in self.roles.values()
                     if sr.data is not None)
        padded = sum(sr.padded_bits for sr in self.roles.values()
                     if sr.data is not None)
        return padded / stored if stored else 1.0

    # -- integrity ----------------------------------------------------------
    def verify(self) -> dict[str, str]:
        """Verify the SERVING representation: per-layer structural checks on
        the stacked slices plus content digests re-derived from the logical
        (un-padded) encoding, compared against ``plan.checksums``.  Raises
        :class:`repro.runtime.integrity.IntegrityError` on violation;
        dense-kind roles carry no stacked payload and are skipped."""
        from repro.runtime import integrity
        return integrity.verify(self)


def _by_expert(arrays: list, experts: int) -> jnp.ndarray:
    """Stack per-entry arrays (layer-major, expert-minor) with a leading
    layer axis, and an expert axis after it when ``experts``."""
    out = np.stack([np.asarray(a) for a in arrays])
    return jnp.asarray(out.reshape((-1, experts) + out.shape[1:])
                       if experts else out)


def _stack_bitmap(role: str, entries: list[CompressedTensor],
                  experts: int) -> StackedRole:
    ds = [e.data for e in entries]
    bn, bk = ds[0].bn, ds[0].bk
    n, k = ds[0].n, ds[0].k
    vb = ds[0].blocks.dtype.itemsize * 8
    pad_to = max(max(int(d.blocks.shape[0]) for d in ds), 1)
    blocks, rows = [], []
    for d in ds:
        nnzb = int(d.blocks.shape[0])
        b = np.zeros((pad_to, bn, bk), np.asarray(d.blocks).dtype)
        r = np.zeros((pad_to,), np.int32)
        if nnzb:
            b[:nnzb] = np.asarray(d.blocks)
            r[:nnzb] = np.asarray(d.row_ids)
        blocks.append(b)
        rows.append(r)
    total_nnzb = sum(int(np.asarray(d.counts).sum()) for d in ds)
    stored = sum(e.stored_bits for e in entries)
    return StackedRole(
        role=role, kind="bitmap", n=n, k=k,
        data={"blocks": _by_expert(blocks, experts),
              "row_ids": _by_expert(rows, experts),
              "counts": _by_expert([d.counts for d in ds], experts),
              "offsets": _by_expert([d.offsets for d in ds], experts)},
        bn=bn, bk=bk, experts=experts,
        t_max=max(max(d.max_per_col for d in ds), 1),
        dense_bits=sum(e.dense_bits for e in entries),
        stored_bits=stored,
        padded_bits=stored + (len(ds) * pad_to - total_nnzb) * bn * bk * vb,
        payload_elems=float(total_nnzb * bn * bk),
        decode_units=float(total_nnzb))


def _stack_nm(role: str, entries: list[CompressedTensor],
              experts: int) -> StackedRole:
    ds = [e.data for e in entries]
    stored = sum(e.stored_bits for e in entries)
    return StackedRole(
        role=role, kind="nm", n=ds[0].n, k=ds[0].k,
        data={"values": _by_expert([d.values for d in ds], experts),
              "indices": _by_expert([d.indices for d in ds], experts)},
        n_sel=ds[0].n_sel, m_group=ds[0].m_group, experts=experts,
        dense_bits=sum(e.dense_bits for e in entries),
        stored_bits=stored, padded_bits=stored,
        payload_elems=float(sum(d.values.size for d in ds)),
        decode_units=float(sum(d.indices.size for d in ds)))


def _stack_dense(role: str, entries: list[CompressedTensor],
                 experts: int) -> StackedRole:
    d0 = entries[0].data
    stored = sum(e.stored_bits for e in entries)
    return StackedRole(
        role=role, kind="dense", n=d0.shape[0], k=d0.shape[1], data=None,
        experts=experts,
        dense_bits=sum(e.dense_bits for e in entries),
        stored_bits=stored, padded_bits=stored,
        payload_elems=float(sum(e.data.size for e in entries)),
        decode_units=0.0)


def stack_store(store: CompressedStore) -> StackedStore:
    """Re-lay ``store`` with a leading layer axis per role, and an expert
    axis after it for a MoE role's held experts (arrays (layer, expert,
    …)).  Bitmap payloads pad to the max non-zero block count across
    layers and experts; padded blocks are zeros with row id 0 and sit
    beyond every column's ``counts``, so the kernel never accumulates them
    — results are bit-identical to the per-layer dispatch."""
    n_layers = store.plan.n_layers
    by_role: dict[str, list[CompressedTensor]] = {}
    for e in store:
        by_role.setdefault(e.role, []).append(e)
    roles: dict[str, StackedRole] = {}
    for role, entries in by_role.items():
        entries.sort(key=lambda e: (e.layer, e.expert))
        experts = len({e.expert for e in entries}) if entries[0].expert >= 0 \
            else 0
        if len(entries) != n_layers * max(experts, 1):
            raise ValueError(f"role {role!r} has {len(entries)} entries for "
                             f"{n_layers} layers of {max(experts, 1)}")
        kind = entries[0].kind
        if any(e.kind != kind for e in entries):
            raise ValueError(f"role {role!r} mixes kinds across layers")
        stack = {"bitmap": _stack_bitmap, "nm": _stack_nm,
                 "dense": _stack_dense}[kind]
        roles[role] = stack(role, entries, experts)
    return StackedStore(plan=store.plan, n_layers=n_layers, roles=roles)


def prune_params(params: dict, plan: ExecPlan, cfg: ModelConfig) -> dict:
    """Prune ``params`` to the plan's servable sparsity structure.

    Bitmap roles get block pruning at the plan's block shape and target
    density; nm roles get 2:4 pruning; dense roles pass through.  Returns a
    new pytree (the input is not mutated) — the dense REFERENCE forward
    should run on this same pruned tree so compressed-vs-dense comparisons
    isolate kernel numerics, not pruning error."""
    _check_uniform(cfg)
    sp = plan.sparsity
    density = sp.density
    n_sel, m_group = (sp.n, sp.m) if isinstance(sp, NM) else (2, 4)
    blocks = dict(params["blocks"])          # group dicts copied on write
    out = dict(params)
    out["blocks"] = blocks
    for op in plan.ops:
        ch = op.choice
        if ch.kind == "dense":
            continue
        group, leaf = _role_path(op.role)
        w = blocks[group][leaf]

        def prune_one(w2d):
            if ch.kind == "bitmap":
                return masks.block_prune(w2d, ch.block_n, ch.block_k, density)
            return masks.nm_prune(w2d, n_sel, m_group)

        if w.ndim == 3:                               # (L, n, k)
            pruned = jnp.stack([prune_one(w[l]) for l in range(w.shape[0])])
        else:                                         # (L, E, n, k) — MoE
            pruned = jnp.stack([
                jnp.stack([prune_one(w[l, e]) for e in range(w.shape[1])])
                for l in range(w.shape[0])])
        blocks[group] = dict(blocks[group])
        blocks[group][leaf] = pruned
    return out
