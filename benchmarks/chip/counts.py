"""The chip's peaks, and the operations and bytes the served work needs.

Everything here is computed from shapes and dtypes (of the served arrays,
or of the configuration), never measured, so that a roofline share or a
utilization divides a fixed amount of work by a measured time.  What
depends on the architecture (the FLOPs a decoded token needs, the weight
bytes a step reads) is its module's (``arch/<name>.py``); what is here
serves every one.
"""

from __future__ import annotations

import dataclasses

#: Published peaks of one chip, keyed by ``jax.Device.device_kind``.
#: Source: Google Cloud documentation, "TPU v5e" (system architecture):
#: 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s.
PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
    "TPU v5e": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a device missing from the table is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


@dataclasses.dataclass(frozen=True)
class KernelRole:
    """One kernel-served projection as it is served: ``(n, k)`` weight in
    ``(bn, bk)`` payload blocks, ``nnzb`` of them non-zero in each layer."""

    role: str
    n: int
    k: int
    bn: int
    bk: int
    nnzb: int
    payload_itemsize: int
    meta_bytes: int            # metadata one call reads, per layer


def bitmap_call_cost(r: KernelRole, m: int, x_itemsize: int,
                     out_itemsize: int = 4) -> tuple[float, float]:
    """(FLOPs, bytes) of one ``bitmap_spmm`` call over ``m`` rows: one pass
    over the non-zero payload blocks and the metadata, the activations
    ``x`` (m, n) read once and the float32 output (m, k) written once."""
    flops = 2.0 * m * r.nnzb * r.bn * r.bk
    nbytes = (r.nnzb * r.bn * r.bk * r.payload_itemsize + r.meta_bytes
              + m * r.n * x_itemsize + m * r.k * out_itemsize)
    return flops, float(nbytes)


def least_time_s(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound."""
    return max(flops / peak["flops_bf16"], nbytes / peak["hbm_bytes_per_s"])


def served_kernel_roles(stacked, nnzb: dict[str, int]) -> list[KernelRole]:
    """The bitmap roles of a served layer-stacked store, with the payload
    and metadata dtypes of the arrays it serves.  ``nnzb``: non-zero blocks
    per layer of each role, as the benchmark drew them."""
    out = []
    for role, sr in stacked.roles.items():
        if sr.kind != "bitmap" or sr.data is None:
            continue
        d = sr.data
        gk = int(d["counts"].shape[-1])
        meta = (nnzb[role] * d["row_ids"].dtype.itemsize
                + gk * (d["counts"].dtype.itemsize
                        + d["offsets"].dtype.itemsize))
        out.append(KernelRole(role=role, n=sr.n, k=sr.k, bn=sr.bn, bk=sr.bk,
                              nnzb=nnzb[role],
                              payload_itemsize=d["blocks"].dtype.itemsize,
                              meta_bytes=int(meta)))
    return out


def kv_bytes_per_position(cache) -> float:
    """Bytes of K and V one cached position holds over all layers: a slot
    at context c reads c times this."""
    import jax
    total = 0.0
    for leaf in jax.tree.leaves(cache):
        if leaf.ndim == 5:                   # (layers, slots, len, kv, hd)
            total += leaf.shape[0] * leaf.shape[3] * leaf.shape[4] \
                * leaf.dtype.itemsize
    return total
