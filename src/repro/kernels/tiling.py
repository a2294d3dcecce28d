"""Tile shapes the TPU's Pallas compiler (Mosaic) accepts for the sparse
kernels, and the on-chip memory (VMEM) budget they are sized against.

Mosaic slices and blocks an array along its last two axes only in whole
hardware tiles: a multiple of 128 lanes along the last axis, and a multiple
of the dtype's sublane packing along the one before it (8 rows at 32 bits,
16 at bf16, 32 at 8 bits) — unless the block spans the whole axis.  The
interpret mode the CPU runs accepts anything, so these rules are enforced
here, where plans and dispatchers pick shapes, and pinned by compiling the
kernels for a described v5e in ``tests/test_tpu_compile.py``.

Pure Python: the planner sizes bitmap blocks with it at plan time and the
dispatchers pick row tiles with it at trace time.
"""

from __future__ import annotations

LANE = 128

#: Most rows one kernel grid step takes (the MXU's native height).
ROW_TILE_CAP = 128

#: VMEM one sparse-kernel call may fill with its double-buffered activation,
#: payload and output tiles.  Mosaic's default scoped-VMEM limit on v5e is
#: 16 MiB; half of it leaves room for the compiler's own temporaries (the
#: activation cast feeding the MXU, the dot result before accumulation).
VMEM_BUDGET_BYTES = 8 * 2**20


def sublane(itemsize: int) -> int:
    """Row multiple Mosaic tiles a ``itemsize``-byte dtype by: 8 for 32-bit,
    16 for bf16, 32 for 8-bit values."""
    return 8 * max(1, 4 // itemsize)


def row_tile(m: int, itemsize: int, cap: int = ROW_TILE_CAP) -> int:
    """Row tile for an ``m``-row activation of ``itemsize``-byte values.

    Takes the fewest tiles of at most ``cap`` rows and rounds the rows each
    gets up to the dtype's sublane multiple; the kernel wrappers pad ``m``
    to a multiple of the tile and slice the padding off the output.  So
    m=130 runs as 2 tiles of 80 bf16 rows (160 padded) instead of 65-row
    tiles Mosaic refuses, and m=1 as one 16-row tile."""
    sub = sublane(itemsize)
    cap = max(sub, cap - cap % sub)
    tiles = -(-max(m, 1) // cap)
    per = -(-max(m, 1) // tiles)
    return -(-per // sub) * sub


def lane_divisors(extent: int) -> list[int]:
    """Block widths Mosaic accepts along a last (lane) axis of ``extent``,
    ascending: the multiples of 128 that divide it, or the whole axis when
    ``extent`` is not a multiple of 128."""
    if extent % LANE:
        return [extent]
    return [d for d in range(LANE, extent + 1, LANE) if extent % d == 0]


def lane_tile(extent: int, cap: int = LANE) -> int:
    """The widest lane-aligned block of ``extent`` that is at most ``cap``
    (the narrowest one when none is)."""
    ds = lane_divisors(extent)
    fit = [d for d in ds if d <= cap]
    return fit[-1] if fit else ds[0]


def bitmap_tile_bytes(bn: int, bk: int, value_bytes: int,
                      bm: int = ROW_TILE_CAP) -> int:
    """VMEM the bitmap kernel's double-buffered tiles take for a (bn, bk)
    payload block: activation (bm, bn) at up to 4 bytes, payload (bn, bk)
    at ``value_bytes``, float32 output (bm, bk)."""
    return 2 * (bm * bn * 4 + bn * bk * value_bytes + bm * bk * 4)


def bitmap_block(n: int, k: int, bn_hint: int, bk_hint: int,
                 value_bytes: int = 4) -> tuple[int, int]:
    """Executable (bn, bk) block of an (n, k) bitmap-compressed weight.

    Both sides are lane-aligned divisors (:func:`lane_divisors`) no larger
    than the searched leaf sizes ``bn_hint`` / ``bk_hint`` (the narrowest
    one when the leaf is narrower than a lane).  The larger side then
    shrinks, one divisor at a time, until :func:`bitmap_tile_bytes` fits
    :data:`VMEM_BUDGET_BYTES`."""
    ns, ks = lane_divisors(n), lane_divisors(k)
    i = max([a for a, d in enumerate(ns) if d <= bn_hint], default=0)
    j = max([a for a, d in enumerate(ks) if d <= bk_hint], default=0)
    while bitmap_tile_bytes(ns[i], ks[j], value_bytes) > VMEM_BUDGET_BYTES:
        if j > 0 and (ks[j] >= ns[i] or i == 0):
            j -= 1
        elif i > 0:
            i -= 1
        else:
            break
    return ns[i], ks[j]
