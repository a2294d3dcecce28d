"""``correct`` separates the served path from its control and from a
broken timed path, on a run of a tiny cell on the CPU (the harness's look
for a chip skipped, everything else as on the chip)."""

import json

import numpy as np

import run


def _run(bench, capsys, extra=(), sabotage=None, workload="chatglm3-6b.decode",
         err=False):
    argv = ["--workload", workload, "--seed", str(2**31 + 77),
            "--seconds", "2", "--trace", "0", *extra]
    assert run.main(argv, here=str(bench), bench_root=str(bench),
                    require_chip=False, cache=False, sabotage=sabotage) == 0
    got = capsys.readouterr()
    out = json.loads(got.out.strip().splitlines()[-1])
    return (out, got.err) if err else out


def test_served_path_passes_and_float8_control_fails(tiny_bench, capsys):
    out, err = _run(tiny_bench, capsys, extra=["--control", "bf16,fp8"],
                    err=True)
    # the program, checked on the same run
    assert "program: correct True" in err
    assert "program: check slots_checked = 4 (limit 4)" in err
    # each control in its place reads not correct, and so does the line
    assert "control bf16: correct False" in err
    assert "control fp8: correct False" in err
    assert out["correct"] is False
    assert list(out)[-1] == "checks"
    checks = out["checks"]
    assert checks["fp8.logit_gap"]["value"] > checks["fp8.logit_gap"]["limit"]
    assert checks["bf16.payload_dtype"] == {"value": "bfloat16",
                                            "limit": "float32"}


def test_payload_served_below_the_stated_precision_fails():
    import jax.numpy as jnp
    from types import SimpleNamespace as NS

    import check
    blocks = jnp.zeros((1, 2, 8, 8), jnp.bfloat16)
    stacked = NS(roles={"attn.wq": NS(data={"blocks": blocks}),
                        "attn.wk": NS(data=None)})
    params = {"blocks": {"attn": {"wk": jnp.zeros((8, 8), jnp.float32)}}}
    assert run.served_dtype(stacked, params) == "bfloat16+float32"
    ok = {"payload_dtype": {"value": "float32", "limit": "float32"}}
    low = {"payload_dtype": {"value": "bfloat16", "limit": "float32"}}
    assert check.judge(ok) and not check.judge(low)


def test_sample_spreads_over_slots():
    from types import SimpleNamespace as NS

    import check
    # slot 0 serves one long request, slot 1 many short ones
    finished = {"long": (0, np.zeros(4, np.int32))}
    results = {"long": NS(n_tokens=900, tokens=np.zeros(900, np.int32))}
    for i in range(20):
        finished[f"s{i}"] = (1 if i < 17 else i - 15, np.zeros(4, np.int32))
        results[f"s{i}"] = NS(n_tokens=10, tokens=np.zeros(10, np.int32))
    picked = check.sample(finished, results, seed=2**31 + 5)
    assert picked[0][2].shape == (900,)                 # the longest
    slots = [slot for slot, _, _ in picked]
    assert len(picked) == 4 and len(set(slots)) == 4 and slots[0] == 0


def test_token_altered_where_it_is_produced_fails(tiny_bench, capsys):
    def sabotage(driver):
        mx = driver.mx
        emit = mx._emit
        vocab = mx.model.cfg.vocab

        def altered(slot, tok):
            # every third token served is off by one
            if mx.tokens_out % 3 == 2:
                tok = (tok + 1) % vocab
            return emit(slot, tok)
        mx._emit = altered

    out = _run(tiny_bench, capsys, sabotage=sabotage)
    assert out["correct"] is False
    checks = out["checks"]
    assert checks["logit_gap"]["value"] > checks["logit_gap"]["limit"]


def test_open_loop_run_reports_its_metrics(tiny_bench, capsys):
    out = _run(tiny_bench, capsys, workload="chatglm3-6b.chat")
    assert out["correct"] is True
    assert set(out["metrics"]) == {"itl_p50_ms", "itl_p99_ms", "setup_s"}
    assert all(np.isfinite(m["value"]) and m["value"] > 0
               for m in out["metrics"].values())
    assert out["device"]["count"] == 1
