"""Compile-only tests: the sparse kernels of chatglm3-6b's serving plan at
published widths, compiled by Mosaic for a described TPU v5e — no chip.

Interpret mode (every other kernel test) accepts any block shape; Mosaic
refuses a lane slice that is not a multiple of 128, a row tile that is not
a multiple of the dtype's sublane packing, and tiles that overflow VMEM.
These tests compile exactly what the serving path dispatches: the bitmap
block of every role of ``launch.serve._fast_plan`` (the plan
``compressed_model`` serves), the N:M tiles ``ops.nm_spmm`` defaults to,
and the row tile and padding of the kernel wrappers, for bf16 activations
and fp32 payloads at decode- and prefill-sized row counts.

The topology is described inside a module fixture (never at import: only
one process may load the TPU library, and the test workers all import this
file), which skips when it cannot be described.  The persistent compile
cache is off inside it: an entry compiled for a described chip cannot be
read back without one.
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops, tiling
from repro.launch.serve import _fast_plan

CFG = dataclasses.replace(get_config("chatglm3-6b"), n_layers=4)
ROLES = tuple(r.role for r in CFG.matmul_roles())
ROWS = (1, 3, 8, 37, 130, 200, 512)
N_SEL, M_GROUP = 2, 4


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                   # noqa: BLE001 — reported as skip
        jax.config.update("jax_enable_compilation_cache", enabled)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def plan():
    # compressed_model's default planning budget (tokens=64)
    return {op.role: op for op in _fast_plan(CFG, tokens=64).ops}


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("m", ROWS)
@pytest.mark.parametrize("role", ROLES)
def test_bitmap_kernel_compiles_for_v5e(one_chip, plan, role, m):
    op = plan[role]
    assert op.choice.kind == "bitmap", op.choice
    bn, bk = op.choice.block_n, op.choice.block_k
    gn, gk = op.n // bn, op.k // bk
    nnzb = max(gn * gk // 2, 1)             # density-0.5 block pruning
    x = _spec((m, op.n), jnp.bfloat16, one_chip)
    fn = ops._bitmap_builder(op.k, ops._row_tile(x, None), gn,
                             pipeline=True, interpret=False)
    compiled = jax.jit(fn).lower(
        x, _spec((nnzb, bn, bk), jnp.float32, one_chip),
        _spec((gk,), jnp.int32, one_chip),
        _spec((nnzb,), jnp.int32, one_chip),
        _spec((gk,), jnp.int32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("m", ROWS)
@pytest.mark.parametrize("role", ROLES)
def test_nm_kernel_compiles_for_v5e(one_chip, plan, role, m):
    op = plan[role]
    half = op.n * N_SEL // M_GROUP
    x = _spec((m, op.n), jnp.bfloat16, one_chip)
    fn = ops._nm_builder(N_SEL, M_GROUP, ops._row_tile(x, None),
                         tiling.lane_tile(op.n), tiling.lane_tile(op.k),
                         pipeline=True, interpret=False)
    compiled = jax.jit(fn).lower(
        x, _spec((half, op.k), jnp.float32, one_chip),
        _spec((half, op.k), jnp.int8, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
