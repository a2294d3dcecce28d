"""Counts of operations and bytes, the peaks table, and the chip guard."""

import json
import subprocess
import sys

import numpy as np
import pytest

import counts
import spec

CHATGLM = spec.dims(spec.config("chatglm3-6b"))
GQA = spec.arch("gqa")


def _role(itemsize):
    # ffn.w_up of chatglm3-6b as the plan serves it: 4096 x 13696 in
    # 1024 x 1712 blocks... any shape works; the payload dtype is the point
    return counts.KernelRole(role="ffn.w_up", n=4096, k=13696, bn=512,
                             bk=1712, nnzb=32, payload_itemsize=itemsize,
                             meta_bytes=32 * 4 + 8 * 8)


def test_bytes_follow_the_payload_dtype():
    f32 = counts.bitmap_call_cost(_role(4), m=32, x_itemsize=2)
    bf16 = counts.bitmap_call_cost(_role(2), m=32, x_itemsize=2)
    payload = 32 * 512 * 1712
    assert f32[0] == bf16[0] == 2 * 32 * payload
    assert f32[1] - bf16[1] == 2 * payload
    peak = counts.peaks("TPU v5 lite")
    assert counts.least_time_s(*bf16, peak) < counts.least_time_s(*f32, peak)


def test_served_roles_read_the_served_dtypes():
    class SR:
        kind, n, k, bn, bk = "bitmap", 256, 512, 128, 256

        def __init__(self, dt):
            self.data = {"blocks": np.zeros((2, 3, 128, 256), dt),
                         "row_ids": np.zeros((2, 3), np.int32),
                         "counts": np.zeros((2, 2), np.int32),
                         "offsets": np.zeros((2, 2), np.int32)}

    class Stacked:
        roles = {"ffn.w_up": SR(np.float32), "attn.wq": SR(np.float16)}

    got = {r.role: r for r in counts.served_kernel_roles(
        Stacked(), {"ffn.w_up": 2, "attn.wq": 2})}
    assert got["ffn.w_up"].payload_itemsize == 4
    assert got["attn.wq"].payload_itemsize == 2
    assert got["ffn.w_up"].meta_bytes == 2 * 4 + 2 * (4 + 4)


def test_unknown_device_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        counts.peaks("TPU v9 imaginary")
    assert counts.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_decode_flops_count_weights_head_and_context():
    dims = dict(CHATGLM, n_layers=1)
    one = GQA.decode_token_flops(dims, 1000, [1])
    two = GQA.decode_token_flops(dims, 1000, [1, 101])
    head = 2 * dims["vocab"] * dims["d_model"]
    per_ctx = 4 * dims["n_heads"] * dims["head_dim"]
    assert one == 2 * 1000 + head + per_ctx
    assert two - 2 * one == 100 * per_ctx


def test_harness_exits_nonzero_without_a_tpu():
    """On the CPU backend the benchmark exits 1 and prints no result."""
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "chatglm3-6b.decode", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=spec.ROOT, capture_output=True, text=True,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}, timeout=300)
    assert p.returncode == 1
    assert "no TPU" in p.stderr
    for line in p.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)


def test_harness_exits_nonzero_without_the_program(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files."""
    import shutil
    shutil.copy(f"{spec.ROOT}/BENCHMARK.json", tmp_path)
    shutil.copytree(spec.HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "chatglm3-6b.decode", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, capture_output=True, text=True,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_step_bytes_count_kernel_payload_dense_roles_and_head():
    class SR:
        def __init__(self, kind):
            self.kind = kind

    class Stacked:
        roles = {"ffn.w_up": SR("bitmap"), "attn.wq": SR("dense")}

    params = {"embed": np.zeros((100, 8), np.float32),
              "blocks": {"attn": {"wq": np.zeros((2, 8, 16), np.float16)},
                         "ffn": {"w_up": np.zeros((2, 8, 32), np.float32)}}}
    role = counts.KernelRole("ffn.w_up", 8, 32, 8, 16, 1, 4, 24)
    got = GQA.step_weight_bytes({"kernel_roles": [role],
                                 "stacked": Stacked(), "params": params,
                                 "dims": {"n_layers": 2}})
    assert got == 2 * (8 * 16 * 4 + 24) + 2 * 8 * 16 * 2 + 100 * 8 * 4
    cache = {"self": {"k": np.zeros((2, 4, 64, 2, 16), np.float16),
                      "v": np.zeros((2, 4, 64, 2, 16), np.float16)}}
    assert counts.kv_bytes_per_position(cache) == 2 * (2 * 2 * 16 * 2)
