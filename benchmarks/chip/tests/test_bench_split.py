"""``split.py`` on a tiny cell on the CPU: both profiled windows run, the
compiled decode step's HLO text carries the program's scopes, and the
output line holds the readings.  A CPU trace has no TPU plane, so the two
reductions are stood in for; the lowering of the mixer's step is real."""

import json

import scopes
import split
import tracing


def test_split_lowers_the_step_and_reports_its_readings(tiny_bench, capsys,
                                                         monkeypatch,
                                                         tmp_path):
    fake_trace = tracing.Trace(window_s=1.0, busy_s=0.5, steps=[], admits=[],
                               breakdown={"device_ops": [["fusion.1", 0.1]],
                                          "idle_gaps": []})
    reduced, seen = [], {}

    def trace_reduce(path, chips=1):
        reduced.append(path)
        return fake_trace

    def scope_reduce(path, hlo, chips=1, scopes=scopes.SCOPES):
        seen.update(hlo=hlo, scopes=scopes)
        return split.scopes.Steps(
            steps=3, scope_ms={s: 1.0 for s in scopes},
            idle_ms={p: 0.5 for p in split.scopes.SYNC_PHASES
                     + split.scopes.HOST_PHASES}, unscoped=0)

    monkeypatch.setattr(tracing, "reduce", trace_reduce)
    monkeypatch.setattr(scopes, "reduce", scope_reduce)
    out_dir = tmp_path / "out"
    argv = ["--workload", "chatglm3-6b.decode", "--seed", str(2**31 + 5),
            "--seconds", "1", "--out", str(out_dir)]
    assert split.main(argv, root=str(tmp_path), here=str(tiny_bench),
                      bench_root=str(tiny_bench), require_chip=False,
                      cache=False) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])

    assert len(reduced) == 2                  # spans on, then off
    assert set(out) >= {"on", "off", "scope_ms", "idle_ms", "top_ops"}
    assert out["sync_idle_ms"] == 0.5 and out["host_idle_ms"] == 1.5
    hlo = seen["hlo"]
    assert (out_dir / "chatglm3-6b.decode.hlo.txt").read_text() == hlo
    paths = scopes.hlo_op_names(hlo).values()
    for scope in ("decode", "attention", "kv_write", "head", "attn.wq",
                  "ffn.w_down"):
        assert any(scopes.has_scope(p, scope) for p in paths), scope
    assert not (tmp_path / ".bench_trace").exists()
    assert not (tmp_path / ".bench_split.xplane.pb").exists()
