#!/usr/bin/env python3
"""Cut the trace that ``record_trace.py`` recorded on a v5e down to the
fixture ``v5e_trace.pbtxt``: the first admission and the first decode step,
with the device's ``XLA Ops`` events that overlap them and the harness's
annotations, in XSpace text form.

Mosaic kernel events are kept one by one, named by their op head
(``%call.57 = f32[16,512]``) and custom-call target.  The other ops are
kept as the union of their intervals, one ``%merged.<i>`` event per busy
stretch, which leaves the busy time as it was.  The probe's admissions
carried no ``plen`` stat; the cut adds the prompt length the probe
admitted (128).

  python benchmarks/chip/tests/fixtures/cut_trace.py <probe .xplane.pb>
"""

from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'
PLEN = 128


def _short(name: str) -> str:
    head = name.split(" = ", 1)
    if len(head) == 1:
        return name
    shape = head[1].split(" ", 1)[0]
    tail = f" custom-call(), {KERNEL_TARGET}" if KERNEL_TARGET in name \
        else " fusion()"
    return f"{head[0]} = {shape}{tail}"


def main(path: str) -> None:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    host = [ev for pl in pd.planes if pl.name == "/host:CPU"
            for ln in pl.lines for ev in ln.events
            if ev.name.startswith("bench.")]
    admit = min((e for e in host if e.name == "bench.admit"),
                key=lambda e: e.start_ns)
    step = min((e for e in host if e.name == "bench.step"),
               key=lambda e: e.start_ns)
    t0 = int(admit.start_ns) - 1000
    keep = [(admit.start_ns, admit.end_ns), (step.start_ns, step.end_ns)]

    def inside(ev):
        return any(ev.start_ns < b and ev.end_ns > a for a, b in keep)

    names: dict[str, int] = {}

    def mid(name):
        return names.setdefault(name, len(names) + 1)

    def ev_txt(ev, stat=""):
        return (f"    events {{ metadata_id: {mid(_short(ev.name))} "
                f"offset_ps: {int(round((ev.start_ns - t0) * 1000))} "
                f"duration_ps: {int(round(ev.duration_ns * 1000))}{stat} }}")

    dev = [pl for pl in pd.planes if pl.name == "/device:TPU:0"][0]
    out = ["# Cut from a v5e trace of the compressed serving path "
           "(record_trace.py, cut_trace.py).",
           "planes {", "  id: 1", '  name: "/device:TPU:0"']
    ops = [ev for ln in dev.lines if ln.name == "XLA Ops"
           for ev in ln.events if inside(ev) and ev.duration_ns > 0]
    kernels = [ev for ev in ops if KERNEL_TARGET in ev.name]
    merged: list = []
    for ev in sorted((e for e in ops if KERNEL_TARGET not in e.name),
                     key=lambda e: e.start_ns):
        if merged and ev.start_ns <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], ev.end_ns)
        else:
            merged.append([ev.start_ns, ev.end_ns])
    out.append(f'  lines {{ id: 1 name: "XLA Ops" timestamp_ns: {t0}')
    for i, (a, b) in enumerate(merged):
        out.append(ev_txt(_Ev(f"%merged.{i} = ops fusion()", a, b)))
    out += [ev_txt(ev) for ev in kernels]
    out.append("  }")
    out += [f"  event_metadata {{ key: {v} value {{ id: {v} name: "
            f"{_quote(k)} }} }}" for k, v in names.items()]
    out.append("}")
    names.clear()
    out += ["planes {", "  id: 2", '  name: "/host:CPU"',
            f'  lines {{ id: 1 name: "python" timestamp_ns: {t0}',
            ev_txt(admit, f" stats {{ metadata_id: 1 int64_value: {PLEN} }}"),
            ev_txt(step), "  }"]
    out += [f"  event_metadata {{ key: {v} value {{ id: {v} name: "
            f"{_quote(k)} }} }}" for k, v in names.items()]
    out += ['  stat_metadata { key: 1 value { id: 1 name: "plen" } }', "}"]
    with open(os.path.join(HERE, "v5e_trace.pbtxt"), "w") as f:
        f.write("\n".join(out) + "\n")


class _Ev:
    def __init__(self, name, start_ns, end_ns):
        self.name, self.start_ns = name, start_ns
        self.duration_ns = end_ns - start_ns


def _quote(s: str) -> str:
    return '"' + s.replace("\\", "\\\\").replace('"', '\\"') + '"'


if __name__ == "__main__":
    main(sys.argv[1])
