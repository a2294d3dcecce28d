"""Reduce a profiler trace of the window to the numbers the metrics read.

The trace is JAX's XSpace (``*.xplane.pb``, or its text form for tests),
read with ``jax.profiler.ProfileData``.  Device and host events share one
clock there.  What is read:

* device busy time: the union of the intervals of the ``XLA Ops`` line of
  each ``/device:TPU:<i>`` plane (ops nest, e.g. a ``while`` holds its body,
  so intervals are merged, never summed);
* the harness's own annotations on the host (``bench.step``, and
  ``bench.admit`` with the prompt length as its ``plen`` stat): each ends in
  a host sync, so the device work it caused lies inside it, and device time
  is attributed to the annotation that holds it;
* Mosaic kernels: ``XLA Ops`` events whose op is a ``custom-call`` with
  ``custom_call_target="tpu_custom_call"``, summed by their own durations.

The traced window runs from the first annotation's start to the last one's
end.  Nothing here matches a function name of the program.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses

KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'


@dataclasses.dataclass
class Span:
    start: float               # ns on the trace's clock
    end: float
    busy_ns: float = 0.0       # device busy time inside
    kernel_ns: float = 0.0     # Mosaic kernel time inside
    kernels: int = 0           # Mosaic kernel events inside
    plen: int = 0              # admissions: prompt tokens
    kind: str = ""


@dataclasses.dataclass
class Trace:
    window_s: float
    busy_s: float              # averaged over the chips read
    steps: list
    admits: list
    breakdown: dict


def load(path: str):
    from jax.profiler import ProfileData
    if path.endswith(".pbtxt"):
        with open(path) as f:
            return ProfileData.from_text_proto(f.read())
    return ProfileData.from_file(path)


def _union(intervals: list) -> list:
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


class _Timeline:
    """Merged busy intervals, with the overlap of any interval."""

    def __init__(self, merged: list):
        self.m = merged
        self.starts = [s for s, _ in merged]

    def busy(self, a: float, b: float) -> float:
        i = max(bisect.bisect_right(self.starts, a) - 1, 0)
        total = 0.0
        while i < len(self.m) and self.m[i][0] < b:
            s, e = self.m[i]
            total += max(0.0, min(e, b) - max(s, a))
            i += 1
        return total

    def gaps(self, a: float, b: float) -> list:
        out, t = [], a
        for s, e in self.m:
            if e <= a or s >= b:
                continue
            if s > t:
                out.append((t, s))
            t = max(t, e)
        if t < b:
            out.append((t, b))
        return out


def _op(name: str) -> str:
    head = name.split(" = ", 1)[0].lstrip("%")
    if KERNEL_TARGET in name:
        return f"{head} (Mosaic kernel)"
    return head


def reduce(path: str, chips: int = 1) -> Trace:
    pd = load(path)
    spans: dict[str, list[Span]] = {"bench.step": [], "bench.admit": []}
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and \
                plane.name[len("/device:TPU:"):].isdigit():
            if int(plane.name[len("/device:TPU:"):]) < chips:
                devices.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in spans:
                        stats = dict(ev.stats)
                        spans[ev.name].append(Span(
                            ev.start_ns, ev.end_ns, kind=ev.name,
                            plen=int(stats.get("plen", 0))))
    ann = sorted(spans["bench.step"] + spans["bench.admit"],
                 key=lambda s: s.start)
    if not ann or not devices:
        raise ValueError(f"{path}: no harness annotations or no device plane")
    w0, w1 = ann[0].start, ann[-1].end
    ann_starts = [s.start for s in ann]
    busy_total = 0.0
    by_op: collections.Counter = collections.Counter()
    idle: collections.Counter = collections.Counter()
    for dev in devices:
        ops = [ev for line in dev.lines if line.name == "XLA Ops"
               for ev in line.events if ev.duration_ns > 0]
        tl = _Timeline(_union([(e.start_ns, e.end_ns) for e in ops]))
        busy_total += tl.busy(w0, w1)
        for sp in ann:
            sp.busy_ns += tl.busy(sp.start, sp.end) / len(devices)
        kstarts = sorted((e.start_ns, e.duration_ns) for e in ops
                         if KERNEL_TARGET in e.name)
        ks = [s for s, _ in kstarts]
        for sp in ann:
            i = bisect.bisect_left(ks, sp.start)
            while i < len(ks) and ks[i] < sp.end:
                sp.kernel_ns += kstarts[i][1] / len(devices)
                sp.kernels += 1
                i += 1
        for e in ops:
            if w0 <= e.start_ns < w1 and not e.name.startswith("%while"):
                by_op[_op(e.name)] += e.duration_ns * 1e-9 / len(devices)
        for a, b in tl.gaps(w0, w1):
            inside = 0.0
            j = max(bisect.bisect_right(ann_starts, a) - 1, 0)
            while j < len(ann) and ann[j].start < b:
                part = min(b, ann[j].end) - max(a, ann[j].start)
                if part > 0:
                    idle[f"inside {ann[j].kind} (host dispatch and sync)"] \
                        += part * 1e-9 / len(devices)
                    inside += part
                j += 1
            idle["between calls (scheduler, queue, sleep)"] += \
                (b - a - inside) * 1e-9 / len(devices)
    breakdown = {"device_ops": [[k, v] for k, v in by_op.most_common(10)],
                 "idle_gaps": [[k, v] for k, v in idle.most_common(10)]}
    return Trace(window_s=(w1 - w0) * 1e-9,
                 busy_s=busy_total * 1e-9 / len(devices),
                 steps=spans["bench.step"], admits=spans["bench.admit"],
                 breakdown=breakdown)
