"""N:M (2:4) structured sparse matmul — Pallas TPU kernel.

Weights travel HBM→VMEM compressed: values (N/2, K) + 2-bit positions
(stored int8).  Decompression happens at the VMEM→VREG boundary — each tile
is expanded to a dense (bn, bk) MXU operand with vectorized compares
(no gather), then fed to the systolic matmul.  This is the paper's
``CP``-at-the-innermost-level primitive mapped onto the TPU memory
hierarchy: metadata decode cost sits next to the compute unit, and the
format's group size (4) nests inside the BlockSpec tile exactly as
SnipSnap's efficiency-oriented allocation prescribes.

Two execution paths, selected by ``pipeline`` (mirrors ``bitmap_spmm``):

* **naive**: grid (M/bm, K/bk, N/bn) with BlockSpec-driven per-step
  fetches, accumulating over the N axis.
* **pipelined**: grid (M/bm, K/bk) with a ``fori_loop`` over the N stripes
  and three double-buffered HBM→VMEM DMA streams (x tile, compressed
  values, position indices) so the next stripe's payload transfers overlap
  the current stripe's decode + MAC.

Both paths decode and accumulate the N stripes in the same order with the
same fp32 ``jnp.dot``, so they are bit-identical in interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _decode_tile(wc, idx, *, n_sel: int, m_group: int):
    """Expand a compressed (bh, bk) tile to its dense (bn, bk) operand via
    vectorized position compares — shared by both kernel paths."""
    idx = idx.astype(jnp.int32)
    half, bk = wc.shape
    groups = half // n_sel
    wc3 = wc.reshape(groups, n_sel, bk)
    idx3 = idx.reshape(groups, n_sel, bk)
    # dense[g, p, k] = Σ_j (idx[g,j,k] == p) · wc[g,j,k]
    pos = jax.lax.broadcasted_iota(jnp.int32, (groups, n_sel, m_group, bk), 2)
    eq = idx3[:, :, None, :] == pos
    dense = jnp.sum(jnp.where(eq, wc3[:, :, None, :], 0), axis=1)
    return dense.reshape(groups * m_group, bk)


def _kernel(x_ref, wc_ref, idx_ref, y_ref, *, n_sel: int, m_group: int):
    ni = pl.program_id(2)

    @pl.when(ni == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    dense = _decode_tile(wc_ref[...], idx_ref[...],
                         n_sel=n_sel, m_group=m_group)
    y_ref[...] += jnp.dot(x_ref[...], dense,
                          preferred_element_type=jnp.float32)


def _pipelined_kernel(x_hbm, wc_hbm, idx_hbm, y_ref, *, n_sel: int,
                      m_group: int, bm: int, bn: int, bk: int, gn: int):
    """Double-buffered streaming body: three DMA streams (x / values /
    indices), two VMEM slots each; stripe ``ni+1`` prefetches while stripe
    ``ni`` decodes and MACs."""
    mi = pl.program_id(0)
    kj = pl.program_id(1)
    bh = bn * n_sel // m_group
    y_ref[...] = jnp.zeros_like(y_ref)

    def body(xbuf, wcbuf, idxbuf, sems):
        def dmas(slot, ni):
            return (
                pltpu.make_async_copy(
                    x_hbm.at[pl.ds(mi, 1), :, pl.ds(ni * bn, bn)],
                    xbuf.at[pl.ds(slot, 1)], sems.at[0, slot]),
                pltpu.make_async_copy(
                    wc_hbm.at[pl.ds(ni, 1), :, pl.ds(kj * bk, bk)],
                    wcbuf.at[pl.ds(slot, 1)], sems.at[1, slot]),
                pltpu.make_async_copy(
                    idx_hbm.at[pl.ds(ni, 1), :, pl.ds(kj * bk, bk)],
                    idxbuf.at[pl.ds(slot, 1)], sems.at[2, slot]),
            )

        for c in dmas(0, 0):
            c.start()

        def loop(ni, carry):
            slot = jax.lax.rem(ni, 2)
            nxt = jax.lax.rem(ni + 1, 2)

            @pl.when(ni + 1 < gn)
            def _prefetch():
                for c in dmas(nxt, ni + 1):
                    c.start()

            for c in dmas(slot, ni):
                c.wait()
            dense = _decode_tile(wcbuf[slot], idxbuf[slot],
                                 n_sel=n_sel, m_group=m_group)
            y_ref[...] += jnp.dot(xbuf[slot], dense,
                                  preferred_element_type=jnp.float32)
            return carry

        jax.lax.fori_loop(0, gn, loop, 0)

    bh = bn * n_sel // m_group
    pl.run_scoped(
        body,
        xbuf=pltpu.VMEM((2, bm, bn), x_hbm.dtype),
        wcbuf=pltpu.VMEM((2, bh, bk), wc_hbm.dtype),
        idxbuf=pltpu.VMEM((2, bh, bk), idx_hbm.dtype),
        sems=pltpu.SemaphoreType.DMA((3, 2)),
    )


def _nm_spmm_pipelined(x: jax.Array, wc: jax.Array, idx: jax.Array,
                       *, n_sel: int, m_group: int, bm: int, bn: int,
                       bk: int, interpret: bool) -> jax.Array:
    m, n = x.shape
    half, k = wc.shape
    gn = n // bn
    bh = bn * n_sel // m_group
    # Rank-3 HBM views so DMA src slices are rank-preserving; wc is
    # (half, k) with half == gn·bh, so the reshape is contiguous.
    x3 = x.reshape(m // bm, bm, n)
    wc3 = wc.reshape(gn, bh, k)
    idx3 = idx.reshape(gn, bh, k)
    kernel = functools.partial(_pipelined_kernel, n_sel=n_sel,
                               m_group=m_group, bm=bm, bn=bn, bk=bk, gn=gn)
    return pl.pallas_call(
        kernel,
        grid=(m // bm, k // bk),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 3,
        out_specs=pl.BlockSpec((bm, bk), lambda mi, kj: (mi, kj)),
        out_shape=jax.ShapeDtypeStruct((m, k), jnp.float32),
        interpret=interpret,
        name="nm_spmm_pipelined",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
    )(x3, wc3, idx3)


def nm_spmm_pallas(x: jax.Array, wc: jax.Array, idx: jax.Array,
                   *, n_sel: int = 2, m_group: int = 4,
                   bm: int = 128, bn: int = 128, bk: int = 128,
                   interpret: bool = False,
                   pipeline: bool = False) -> jax.Array:
    """x: (M, N); wc/idx: (N·n/m, K).  Returns (M, K) float32.

    ``pipeline=True`` selects the double-buffered streaming path (see the
    module docstring)."""
    m, n = x.shape
    half, k = wc.shape
    assert half * m_group == n * n_sel, (x.shape, wc.shape)
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    if pipeline:
        return _nm_spmm_pipelined(x, wc, idx, n_sel=n_sel, m_group=m_group,
                                  bm=bm, bn=bn, bk=bk, interpret=interpret)
    bh = bn * n_sel // m_group            # compressed rows per tile
    grid = (m // bm, k // bk, n // bn)

    kernel = functools.partial(_kernel, n_sel=n_sel, m_group=m_group)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda mi, kj, ni: (mi, ni)),
            pl.BlockSpec((bh, bk), lambda mi, kj, ni: (ni, kj)),
            pl.BlockSpec((bh, bk), lambda mi, kj, ni: (ni, kj)),
        ],
        out_specs=pl.BlockSpec((bm, bk), lambda mi, kj, ni: (mi, kj)),
        out_shape=jax.ShapeDtypeStruct((m, k), jnp.float32),
        interpret=interpret,
        name="nm_spmm",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(x, wc, idx)
