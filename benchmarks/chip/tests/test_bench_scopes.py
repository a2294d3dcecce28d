"""Reading the program's own spans and name scopes from a trace."""

import pytest

import scopes


def _ev(meta: int, start_us: float, end_us: float, stat: str = "") -> str:
    return (f"    events {{ metadata_id: {meta} "
            f"offset_ps: {int(start_us * 1e6)} "
            f"duration_ps: {int((end_us - start_us) * 1e6)}{stat} }}")


def _meta(names: list) -> list:
    return [f'  event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
            for i, n in enumerate(names, 1)]


ATTN = "jit(decode_step)/decode/while/body/closed_call/attention/reduce_max"
KV = "jit(decode_step)/decode/while/body/closed_call/kv_write/select_n"

#: Device ops (µs), as a v5e names them (HLO text without metadata): a
#: layer scan [16, 30] holding two attention fusions [17, 21] and [20, 23]
#: and a KV write [24, 26], a kernel [26, 28] of no scope read; the head
#: [31, 33]; the argmax [34, 35], a program of its own whose op shares an
#: instruction name with the decode step's attention fusion.  Step 2: one
#: attention fusion [55, 59].  Before the window, an attention fusion
#: [1, 3] of an untimed step.
DEVICE_OPS = ["%while.1 = (s32[]) while()", "%fusion.10 = f32[8] fusion()",
              "%fusion.11 = f32[8] fusion()", "%select.12 = bf16[8] select()",
              "%call.13 = f32[8] custom-call()",
              "%fusion.14 = f32[8] fusion()", "%fusion.11 = s32[2] reduce()"]
HOST = ["bench.step", "decode_step", "decode_step.inputs",
        "decode_step.dispatch", "decode_step.readback", "decode_step.emit",
        "bench.admit", "admit", "admit.first_token"]

SYNTH = "\n".join([
    'planes { id: 1 name: "/device:TPU:0"',
    '  lines { id: 1 name: "XLA Ops" timestamp_ns: 0',
    _ev(2, 1, 3), _ev(1, 16, 30), _ev(2, 17, 21), _ev(3, 20, 23),
    _ev(4, 24, 26), _ev(5, 26, 28), _ev(6, 31, 33), _ev(7, 34, 35),
    _ev(2, 55, 59), "  }",
    '  lines { id: 2 name: "XLA Modules" timestamp_ns: 0',
    _ev(8, 16, 33), _ev(9, 34, 35), _ev(8, 55, 59), "  }",
    *_meta(DEVICE_OPS + ["jit_decode_step(1)", "jit__argmax(2)"]), "}",
    'planes { id: 2 name: "/host:CPU"',
    '  lines { id: 1 name: "python" timestamp_ns: 0',
    _ev(2, 0, 5),                                     # before the window
    _ev(1, 9, 41), _ev(2, 10, 40), _ev(3, 10, 12), _ev(4, 12, 15),
    _ev(5, 15, 38), _ev(6, 38, 40),
    _ev(1, 49, 71), _ev(2, 50, 70), _ev(3, 50, 51), _ev(4, 51, 53),
    _ev(5, 53, 68), _ev(6, 68, 70),
    _ev(7, 80, 90), _ev(8, 80, 90), _ev(9, 85, 89),
    "  }", *_meta(HOST), "}"])

HLO = f"""HloModule jit_decode_step

%fused_computation.11 (p0: f32[8]) -> f32[8] {{
  %p0 = f32[8] parameter(0)
  %a = f32[8] exponential(f32[8] %p0), metadata={{op_name="{ATTN}"}}
  ROOT %b = f32[8] select(f32[8] %a), metadata={{op_name="{KV}"}}
}}

ENTRY %main.1 (x: f32[8]) -> f32[8] {{
  %x = f32[8] parameter(0)
  %fusion.10 = f32[8] fusion(f32[8] %x), kind=kLoop, calls=%fused_computation.11, metadata={{op_name="{ATTN}"}}
  %fusion.11 = f32[8] fusion(f32[8] %x), kind=kLoop, calls=%fused_computation.11, metadata={{op_name="{ATTN}"}}
  ROOT %select.12 = bf16[8] select(f32[8] %fusion.11), metadata={{op_name="{KV}"}}
}}
"""


@pytest.fixture
def synth(tmp_path):
    path = tmp_path / "t.pbtxt"
    path.write_text(SYNTH)
    return str(path)


def test_scopes_and_idle_phases_per_step(synth):
    """Step 1: attention [17, 23] merged (6 µs), kv_write 2 µs; idle in
    inputs 2, dispatch 3, readback 23 − 17 busy = 6, emit 2.  Step 2:
    attention 4 µs; idle 1, 2, 15 − 4 = 11, 2.  Per step: attention 5,
    kv_write 1, sync 8.5, host 6 (µs)."""
    got = scopes.reduce(synth, HLO)
    assert got.steps == 2
    assert got.scope_ms["attention"] == pytest.approx(5e-3)
    assert got.scope_ms["kv_write"] == pytest.approx(1e-3)
    assert got.sync_idle_ms == pytest.approx(8.5e-3)
    assert got.host_idle_ms == pytest.approx(6e-3)
    assert got.idle_ms == pytest.approx({
        "decode_step.readback": 8.5e-3, "decode_step.inputs": 1.5e-3,
        "decode_step.dispatch": 2.5e-3, "decode_step.emit": 2e-3})
    assert got.unscoped == 4              # while, kernel, head, argmax


def test_trace_without_module_runs_is_refused(synth):
    """Without the ``XLA Modules`` line the argmax op [34, 35] would be read
    by its instruction name as the decode step's attention fusion, so the
    reduction refuses the trace rather than read it so."""
    text = open(synth).read()
    cut = text.index('  lines { id: 2 name: "XLA Modules"')
    no_modules = text[:cut] + text[text.index("  }", cut) + 4:]
    with open(synth, "w") as f:
        f.write(no_modules)
    with pytest.raises(ValueError, match="no run of jit_decode_step"):
        scopes.reduce(synth, HLO)


def test_hlo_text_without_a_module_name_is_refused(synth):
    with pytest.raises(ValueError, match="HloModule"):
        scopes.reduce(synth, HLO.replace("HloModule ", "Module ", 1))


def test_innermost_phase_excludes_nested_program_spans():
    spans = [(0, 10, "decode_step"), (0, 1, "decode_step.inputs"),
             (1, 9, "decode_step.readback"), (3, 4, "admit")]
    assert scopes._innermost(spans, ("decode_step.readback",)) == \
        [(1, 3), (4, 9)]
    assert scopes._innermost(spans, ("decode_step.inputs",)) == [(0, 1)]
    assert scopes._innermost(spans, ("decode_step",)) == [(9, 10)]


def test_hlo_text_maps_ops_to_scopes_and_finds_straddling_fusions():
    names = scopes.hlo_op_names(HLO)
    assert (names["fusion.11"], names["select.12"]) == (ATTN, KV)
    fused = scopes.fusion_scopes(HLO)
    held = {s for p in fused["fusion.11"] for s in scopes.SCOPES
            if scopes.has_scope(p, s)}
    assert held == {"attention", "kv_write"}
    assert scopes.has_scope(ATTN, "attention")
    assert not scopes.has_scope(ATTN + "_x", "kv_write")
