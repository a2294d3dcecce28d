"""Block-bitmap compressed matmul — Pallas TPU kernel.

Executes SnipSnap's TPU-native hierarchical format
``B(N₁)-B(K₁)-None(N₂,K₂)``: a bitmap over the (N/bn, K/bk) block grid with
dense MXU-aligned payload blocks, stored COMPRESSED (only non-zero blocks
travel HBM→VMEM).  The bitmap is pre-decoded on the host into CSC-style
scalar-prefetch metadata (per-block-column counts / offsets / row ids), so
the kernel's grid walks exactly the non-zero blocks — the TPU analogue of
"Skipping I←W" at block granularity (DESIGN.md §4).

Two execution paths, selected by ``pipeline``:

* **naive** (the seed path, kept as the parity/benchmark reference):
  grid (M/bm, K/bk, T) with T = max non-zero blocks in any block-column.
  Every block-column walks the full T steps (`pl.when` masks the short
  ones), and each step's payload fetch is issued by the BlockSpec machinery
  one grid step at a time.
* **pipelined** (the streaming path): grid (M/bm, K/bk) with a manual
  double-buffered async-copy pipeline inside the kernel.  Payload and
  input blocks live in HBM (``memory_space=ANY``); the kernel walks ONLY
  ``counts[kj]`` real blocks per column and overlaps the next block's
  HBM→VMEM DMA with the current block's MAC via two-slot VMEM buffers.
  The per-``kj`` block loop also reads ``row_ids[offsets[kj] : +counts]``
  as one coalesced stripe instead of the naive path's per-grid-step
  scalar gathers.

Both paths accumulate into Y[mi, kj] in the SAME block order with the same
``jnp.dot(..., preferred_element_type=f32)``, so their fp32 results are
bit-identical — in interpret mode (CPU CI) and compiled alike.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(counts_ref, offs_ref, rows_ref, x_ref, w_ref, y_ref):
    kj = pl.program_id(1)
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(t < counts_ref[kj])
    def _acc():
        y_ref[...] += jnp.dot(x_ref[...], w_ref[0],
                              preferred_element_type=jnp.float32)


def _pipelined_kernel(counts_ref, offs_ref, rows_ref, x_hbm, w_hbm, y_ref,
                      *, bm: int, bn: int, bk: int):
    """Double-buffered streaming body: two VMEM slots per operand + DMA
    semaphores; slot ``(t+1) % 2`` prefetches block ``t+1`` while slot
    ``t % 2`` feeds the MXU.  All DMA src/dst indexing is rank-preserving
    (``pl.ds`` slices) so the interpret-mode discharge produces the exact
    same copies the TPU DMA engine would."""
    mi = pl.program_id(0)
    kj = pl.program_id(1)
    n_blk = counts_ref[kj]
    off = offs_ref[kj]
    y_ref[...] = jnp.zeros_like(y_ref)

    def body(xbuf, wbuf, sems):
        def dma_x(slot, t):
            r = rows_ref[off + t]
            return pltpu.make_async_copy(
                x_hbm.at[pl.ds(mi, 1), :, pl.ds(r * bn, bn)],
                xbuf.at[pl.ds(slot, 1)], sems.at[0, slot])

        def dma_w(slot, t):
            return pltpu.make_async_copy(
                w_hbm.at[pl.ds(off + t, 1)], wbuf.at[pl.ds(slot, 1)],
                sems.at[1, slot])

        @pl.when(n_blk > 0)
        def _warm():
            dma_x(0, 0).start()
            dma_w(0, 0).start()

        def loop(t, carry):
            slot = jax.lax.rem(t, 2)
            nxt = jax.lax.rem(t + 1, 2)

            @pl.when(t + 1 < n_blk)
            def _prefetch():
                dma_x(nxt, t + 1).start()
                dma_w(nxt, t + 1).start()

            dma_x(slot, t).wait()
            dma_w(slot, t).wait()
            y_ref[...] += jnp.dot(xbuf[slot], wbuf[slot],
                                  preferred_element_type=jnp.float32)
            return carry

        jax.lax.fori_loop(0, n_blk, loop, 0)

    pl.run_scoped(
        body,
        xbuf=pltpu.VMEM((2, bm, bn), x_hbm.dtype),
        wbuf=pltpu.VMEM((2, bn, bk), w_hbm.dtype),
        sems=pltpu.SemaphoreType.DMA((2, 2)),
    )


def _bitmap_spmm_pipelined(x: jax.Array, blocks: jax.Array,
                           counts: jax.Array, row_ids: jax.Array,
                           offsets: jax.Array, *, k: int, bm: int,
                           interpret: bool) -> jax.Array:
    m, n = x.shape
    nnzb, bn, bk = blocks.shape
    gk = k // bk
    bm = min(bm, m)
    # Rank-3 HBM view of X: DMA src (1, bm, bn) slices match the VMEM slot
    # rank exactly (a rank-preservation requirement of the copy discharge).
    x3 = x.reshape(m // bm, bm, n)
    kernel = functools.partial(_pipelined_kernel, bm=bm, bn=bn, bk=bk)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(m // bm, gk),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((bm, bk), lambda mi, kj, *_: (mi, kj)),
        ),
        out_shape=jax.ShapeDtypeStruct((m, k), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
    )(counts, offsets, row_ids, x3, blocks)


def bitmap_spmm_pallas(x: jax.Array, blocks: jax.Array, counts: jax.Array,
                       row_ids: jax.Array, offsets: jax.Array,
                       *, k: int, bm: int = 128, t_max: int | None = None,
                       interpret: bool = False,
                       pipeline: bool = False) -> jax.Array:
    """x: (M, N) dense; blocks: (nnzb, bn, bk) compressed payload;
    counts/offsets: (K/bk,) per-block-column metadata; row_ids: (nnzb,).
    Returns Y = X @ W_sparse, (M, K) float32.

    ``pipeline=True`` selects the double-buffered streaming path (see the
    module docstring); it needs no ``t_max`` — the in-kernel loop bound is
    the runtime ``counts[kj]``, so short block-columns never pay for the
    longest one.

    ``t_max`` is the NAIVE path's static innermost grid bound (the max
    non-zero blocks in any block-column).  Pass it explicitly whenever
    ``counts`` may be a tracer (jit / scan): the fallback inference must
    then assume ``nnzb``, which walks EVERY stored block per block-column.
    A padded layer-stacked store passes one shared bound so every scanned
    layer runs the same grid.
    """
    if pipeline:
        return _bitmap_spmm_pipelined(x, blocks, counts, row_ids, offsets,
                                      k=k, bm=bm, interpret=interpret)
    m, n = x.shape
    nnzb, bn, bk = blocks.shape
    gk = k // bk
    if t_max is None:
        t_max = 1 if nnzb == 0 else int(counts.max()) \
            if hasattr(counts, "max") \
            and not isinstance(counts, jax.core.Tracer) else nnzb
    # static grid bound: tightest statically-known T
    t_max = max(int(t_max), 1)
    bm = min(bm, m)
    grid = (m // bm, gk, t_max)

    def x_map(mi, kj, t, counts, offs, rows):
        safe_t = jnp.minimum(offs[kj] + t, nnzb - 1)
        return (mi, rows[safe_t])

    def w_map(mi, kj, t, counts, offs, rows):
        return (jnp.minimum(offs[kj] + t, nnzb - 1), 0, 0)

    def y_map(mi, kj, t, counts, offs, rows):
        return (mi, kj)

    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, bn), x_map),
                pl.BlockSpec((1, bn, bk), w_map),
            ],
            out_specs=pl.BlockSpec((bm, bk), y_map),
        ),
        out_shape=jax.ShapeDtypeStruct((m, k), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(counts, offsets, row_ids, x, blocks)
