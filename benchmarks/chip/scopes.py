"""Read the program's own names in a profiler trace of the window.

``tracing.py`` attributes device time to the harness's ``bench.*``
annotations.  This module reads what the program names itself:

* its host spans (``repro.obs.trace.span``), which are
  ``jax.profiler.TraceAnnotation`` events while a profiler records, read by
  the names in :data:`SPANS`;
* each device op's name-scope path (``jax.named_scope`` in the program:
  ``decode``, ``attention``, ``kv_write``, ``head``, a projection's role).
  A v5e's op events carry no scope, only the instruction's HLO text
  without metadata, so the path is the ``op_name`` of that instruction in
  the compiled decode step's HLO text, for ops that run inside that
  program (the device's ``XLA Modules`` events; a trace without them is
  an error, since ops of other programs share instruction names).

From them, per traced decode step (the ``decode_step`` spans in the
window): the device time of ops whose scope path holds a given scope
(nested ops merged, as for busy time), and the device's idle time while
the innermost open program span is a given host phase (``split.py`` runs
a cell and reports them).
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import re

import tracing

#: The program's span names read here (``launch/mixer.py``).  A program
#: change that renames one keeps the old name until the reader moves.
SPANS = ("admit", "prefill", "slot_write", "admit.first_token",
         "decode_step", "decode_step.inputs", "decode_step.dispatch",
         "decode_step.readback", "decode_step.emit")
#: Host phases of a decode step in which the device waits on the host
#: sync (the greedy tokens' read-back) ...
SYNC_PHASES = ("decode_step.readback",)
#: ... and on host work: the step's inputs, its dispatch, the slot loop.
HOST_PHASES = ("decode_step.inputs", "decode_step.dispatch",
               "decode_step.emit")
#: Name scopes of the decode step's device work (``models/attention.py``).
SCOPES = ("attention", "kv_write")

_OP_NAME = re.compile(r'op_name="([^"]*)"')
_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=')
_CALLS = re.compile(r'calls=%?([\w.\-]+)')
_MODULE = re.compile(r'^HloModule\s+([\w.\-]+)')


@dataclasses.dataclass
class Steps:
    steps: int                  # traced decode steps
    scope_ms: dict              # scope → device ms per step
    idle_ms: dict               # host phase → device-idle ms per step
    unscoped: int               # device ops in the steps with no scope read

    @property
    def sync_idle_ms(self) -> float:
        return sum(self.idle_ms[p] for p in SYNC_PHASES)

    @property
    def host_idle_ms(self) -> float:
        return sum(self.idle_ms[p] for p in HOST_PHASES)


def hlo_op_names(text: str) -> dict[str, str]:
    """Instruction name → ``op_name`` metadata in HLO text."""
    out = {}
    for line in text.splitlines():
        m, n = _INSTR.match(line), _OP_NAME.search(line)
        if m and n:
            out[m.group(1)] = n.group(1)
    return out


def fusion_scopes(text: str) -> dict[str, set]:
    """Fusion (or call) instruction → the ``op_name``\\ s of the ops it
    holds, from HLO text: which fusions straddle two scopes."""
    bodies: dict[str, set] = collections.defaultdict(set)
    calls: dict[str, str] = {}
    comp = None
    for line in text.splitlines():
        if line.startswith("}"):
            comp = None
        elif line and not line[0].isspace() and line.endswith("{"):
            comp = line.removeprefix("ENTRY ").split(" ", 1)[0].lstrip("%")
        else:
            m, n, k = _INSTR.match(line), _OP_NAME.search(line), \
                _CALLS.search(line)
            if comp and n:
                bodies[comp].add(n.group(1))
            if m and k:
                calls[m.group(1)] = k.group(1)
    return {ins: bodies[comp] for ins, comp in calls.items()
            if comp in bodies}


def has_scope(path: str, scope: str) -> bool:
    return scope in path.split("/")


def _instr(name: str) -> str:
    return name.split(" = ", 1)[0].lstrip("%").strip()


def _innermost(spans: list, names: tuple) -> list:
    """Intervals in which a span named in ``names`` is the innermost open
    program span: each such span minus the program spans nested in it."""
    starts = [s[0] for s in spans]
    out = []
    for j, (a, b, name) in enumerate(spans):
        if name not in names:
            continue
        inner = []
        i = bisect.bisect_left(starts, a)
        while i < len(spans) and spans[i][0] < b:
            if i != j and spans[i][1] <= b:
                inner.append(spans[i][:2])
            i += 1
        t = a
        for s, e in tracing._union(inner):
            if s > t:
                out.append((t, s))
            t = max(t, e)
        if t < b:
            out.append((t, b))
    return out


def _within(starts: list, intervals: list, t: float) -> bool:
    i = bisect.bisect_right(starts, t) - 1
    return i >= 0 and t < intervals[i][1]


def reduce(path: str, hlo: str, chips: int = 1,
           scopes: tuple = SCOPES) -> Steps:
    pd = tracing.load(path)
    spans, bench, devices = [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and \
                plane.name[len("/device:TPU:"):].isdigit():
            if int(plane.name[len("/device:TPU:"):]) < chips:
                devices.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in SPANS:
                        spans.append((ev.start_ns, ev.end_ns, ev.name))
                    elif ev.name in ("bench.step", "bench.admit"):
                        bench.append((ev.start_ns, ev.end_ns))
    spans.sort()
    if bench:
        w0, w1 = min(a for a, _ in bench), max(b for _, b in bench)
    else:
        w0, w1 = spans[0][0], spans[-1][1]
    steps = [(a, b) for a, b, n in spans
             if n == "decode_step" and w0 <= a and b <= w1]
    if not steps or not devices:
        raise ValueError(f"{path}: no decode_step spans or no device plane")
    names = hlo_op_names(hlo)
    module = _MODULE.match(hlo)
    if module is None:
        raise ValueError("the HLO text names no HloModule")
    step_starts = [a for a, _ in steps]
    scope_ns: collections.Counter = collections.Counter()
    idle_ns: collections.Counter = collections.Counter()
    unscoped = 0
    phases = {p: [(a, b) for a, b in _innermost(spans, (p,))
                  if _within(step_starts, steps, a)]
              for p in SYNC_PHASES + HOST_PHASES}
    for dev in devices:
        ops = [ev for line in dev.lines if line.name == "XLA Ops"
               for ev in line.events if ev.duration_ns > 0]
        runs = sorted((ev.start_ns, ev.end_ns) for line in dev.lines
                      if line.name == "XLA Modules" for ev in line.events
                      if ev.name.split("(")[0] == module[1])
        if not any(a < steps[-1][1] and b > steps[0][0] for a, b in runs):
            raise ValueError(f"{path}: no run of {module[1]} on {dev.name} "
                             f"in the traced decode steps")
        run_starts = [a for a, _ in runs]
        by_scope: dict[str, list] = {s: [] for s in scopes}
        for ev in ops:
            if not _within(step_starts, steps, ev.start_ns):
                continue
            path_ = None
            if _within(run_starts, runs, ev.start_ns):
                path_ = names.get(_instr(ev.name))
            if path_ is None:
                unscoped += 1
                continue
            for s in scopes:
                if has_scope(path_, s):
                    by_scope[s].append((ev.start_ns, ev.end_ns))
        for s, iv in by_scope.items():
            tl = tracing._Timeline(tracing._union(iv))
            scope_ns[s] += sum(tl.busy(a, b) for a, b in steps) / len(devices)
        tl = tracing._Timeline(tracing._union(
            [(e.start_ns, e.end_ns) for e in ops]))
        for p, iv in phases.items():
            idle_ns[p] += sum(b - a - tl.busy(a, b) for a, b in iv) \
                / len(devices)
    n = len(steps)
    return Steps(steps=n,
                 scope_ms={s: 1e-6 * scope_ns[s] / n for s in scopes},
                 idle_ms={k: 1e-6 * idle_ns[k] / n for k in phases},
                 unscoped=unscoped)

