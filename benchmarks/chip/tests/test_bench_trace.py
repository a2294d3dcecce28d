"""Reduction of a device trace to busy time, attribution and kernel time."""

import os

import pytest

import tracing

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "v5e_trace.pbtxt")


def test_recorded_v5e_trace():
    """A decode step and an admission of a 2-layer model with 7 kernel
    roles, cut from a v5e trace."""
    t = tracing.reduce(FIXTURE)
    (step,), (admit,) = t.steps, t.admits
    assert step.kernels == 14                 # 7 roles x 2 layers
    assert admit.plen == 128
    assert 0 < step.kernel_ns <= step.busy_ns <= step.end - step.start
    assert 0 < admit.busy_ns <= admit.end - admit.start
    assert t.window_s == pytest.approx((step.end - admit.start) * 1e-9)
    assert 0 < t.busy_s < t.window_s
    assert t.busy_s == pytest.approx((step.busy_ns + admit.busy_ns) * 1e-9)
    kinds = [k for k, _ in t.breakdown["idle_gaps"]]
    assert "inside bench.step (host dispatch and sync)" in kinds
    assert any("Mosaic kernel" in k for k, _ in t.breakdown["device_ops"])
    assert len(t.breakdown["device_ops"]) <= 10


SYNTH = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 6000000 }
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 4000000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 12000000 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 30000000 duration_ps: 5000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%while.1 = (s32[]) while()" } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.2 = f32[8] fusion()" } }
  event_metadata { key: 3 value { id: 3 name: "%call.3 = f32[8] custom-call(), custom_call_target=\\"tpu_custom_call\\"" } }
}
planes { id: 2 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.9 = f32[8] fusion()" } }
}
planes { id: 3 name: "/host:CPU"
  lines { id: 1 name: "main" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 500000 duration_ps: 13500000 }
    events { metadata_id: 2 offset_ps: 20000000 duration_ps: 20000000 stats { metadata_id: 1 int64_value: 256 } }
    events { metadata_id: 3 offset_ps: 15000000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.step" } }
  event_metadata { key: 2 value { id: 2 name: "bench.admit" } }
  event_metadata { key: 3 value { id: 3 name: "something.else" } }
  stat_metadata { key: 1 value { id: 1 name: "plen" } }
}
"""


def test_nested_ops_merge_and_attribute(tmp_path):
    """Times in µs: step [0.5, 14], admit [20, 40]; ops: a while [1, 7]
    holding a fusion [2, 3] and a kernel [4, 6], a kernel [12, 13], a
    fusion [30, 35].  Device 1 is not read with chips=1."""
    path = tmp_path / "t.pbtxt"
    path.write_text(SYNTH)
    t = tracing.reduce(str(path), chips=1)
    (step,), (admit,) = t.steps, t.admits
    assert step.busy_ns == pytest.approx(1e3 * (6 + 1))
    assert step.kernel_ns == pytest.approx(1e3 * (2 + 1))
    assert step.kernels == 2
    assert admit.busy_ns == pytest.approx(5e3) and admit.plen == 256
    assert admit.kernels == 0
    assert t.window_s == pytest.approx(39.5e-6)
    assert t.busy_s == pytest.approx(12e-6)
    idle = dict(t.breakdown["idle_gaps"])
    assert idle["inside bench.step (host dispatch and sync)"] == \
        pytest.approx((0.5 + 5 + 1) * 1e-6)
    assert idle["inside bench.admit (host dispatch and sync)"] == \
        pytest.approx(15e-6)
    assert idle["between calls (scheduler, queue, sleep)"] == \
        pytest.approx(6e-6)
    ops = dict(t.breakdown["device_ops"])
    assert "while.1" not in ops
    assert ops["call.3 (Mosaic kernel)"] == pytest.approx(3e-6)


def test_chips_average(tmp_path):
    path = tmp_path / "t.pbtxt"
    path.write_text(SYNTH)
    t = tracing.reduce(str(path), chips=2)
    assert t.busy_s == pytest.approx((12 + 39.5) / 2 * 1e-6)


def test_metric_readers_on_the_recorded_trace():
    """Every per-layer reader finds its number in a traced run's context
    (the fixture's trace and a window record of matching shape)."""
    import counts
    import spec
    from window import Record

    t = tracing.reduce(FIXTURE)
    rec = Record(t0=0.0, t_end=10.0)
    rec.steps = [(1.0 + i, 1.03 + i, 4) for i in range(5)]
    rec.admits = [(0.5, 0.52, 128)]
    rec.ctx = [200, 300, 400, 500] * 5
    dims = dict(n_layers=2, d_model=512, n_heads=8, n_kv_heads=2,
                head_dim=64, d_ff=1024, vocab=4096)
    role = counts.KernelRole("ffn.w_up", 512, 1024, 256, 512, 2, 4, 64)
    ctx = {"rec": rec, "trace": t, "dims": dims, "plan_s": 0.1,
           "setup_compile_s": 3.0, "peak": counts.peaks("TPU v5 lite"),
           "kernel_roles": [role], "n_layers": 2, "nnz_layer": 10**6,
           "weight_bytes": 2 * 4 * 10**6 + 4096 * 512 * 4,
           "kv_bytes_per_position": 2 * 2 * 64 * 2 * 2}
    gqa = spec.arch("gqa")
    ctx["decode_flops"] = gqa.decode_flops(ctx)
    ctx["decode_kv_bytes"] = gqa.decode_kv_bytes(ctx)
    bench = spec.benchmark()
    for m in bench["per_layer"]:
        v = spec.metric_reader(m["name"])(ctx)
        assert v is not None and v > 0, m["name"]
        if m["unit"] == "%":
            assert v <= 100.0, (m["name"], v)
