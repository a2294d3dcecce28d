"""The accepted readings of the recorded v5e trace stay as they were first
read: every per-layer metric's value and the reduction's breakdown."""

import os

import pytest

import tracing

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "v5e_trace.pbtxt")

#: Each accepted reading on the fixture, as the benchmark first read them.
FIXTURE_READINGS = {
    "mixer.admit_ms": 20.000000000000018,
    "mixer.busy_slots": 4.0,
    "decode_step.device_ms": 0.096853,
    "prefill.device_us_per_token": 1.2872890625,
    "decode.mfu": 9.774521827411168e-06,
    "decode_step_roofline": 22.468005873785767,
    "bitmap_spmm.device_ms": 0.021741,
    "device.idle_share": 98.37461419561339,
    "setup.plan_s": 0.1,
    "setup.compile_s": 3.0,
}


def _fixture_ctx() -> dict:
    """A traced run's context: the fixture's trace and a window record of
    matching shape."""
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
    return ctx


@pytest.mark.parametrize("name", sorted(FIXTURE_READINGS))
def test_fixture_readings_are_unchanged(name):
    import spec
    assert spec.metric_reader(name)(_fixture_ctx()) == \
        pytest.approx(FIXTURE_READINGS[name], rel=1e-12)


def test_fixture_breakdown_is_unchanged():
    t = tracing.reduce(FIXTURE)
    (step,), (admit,) = t.steps, t.admits
    assert (step.busy_ns, step.kernel_ns, admit.busy_ns) == \
        (96853.0, 21741.0, 164773.0)
    assert [k for k, _ in t.breakdown["device_ops"]] == [
        "merged.48", "merged.32", "merged.7", "merged.3", "merged.27",
        "merged.43", "call.59 (Mosaic kernel)", "call.58 (Mosaic kernel)",
        "call.57 (Mosaic kernel)", "call.56 (Mosaic kernel)"]
    assert dict(t.breakdown["idle_gaps"]) == pytest.approx({
        "inside bench.admit (host dispatch and sync)": 0.007346417,
        "between calls (scheduler, queue, sleep)": 0.00629995,
        "inside bench.step (host dispatch and sync)": 0.002188247})
