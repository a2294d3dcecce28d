"""The measured window: a request stream driven through the mixer.

The driver calls the mixer's admission (``Mixer.admit``) and its one
decode step (``Mixer._step``) itself, because ``Mixer.run`` takes a list
formed at t=0 and an open loop cannot use it.  Every admission ends in a
host sync (its first token is read back) and so does every step (the
greedy tokens are), so the host clock after each call is when its tokens
exist.

Closed loop: every free slot is refilled from the stream at once.  Open
loop: a request enters the queue when it is due; the queue is served first
come, first served, whenever a slot is free.  Time to first token counts
from the due time, so a stall delays every request behind it.  After the
window closes, requests that were due inside it are still admitted and
stepped (no new ones), up to ``DRAIN_S``, until each has its first token;
one that never gets it is a miss, and counts with the time it waited.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from collections import deque

import numpy as np

DRAIN_S = 60.0


@dataclasses.dataclass
class Record:
    t0: float = 0.0
    t_end: float = 0.0
    steps: list = dataclasses.field(default_factory=list)    # (t_a, t_b, busy)
    admits: list = dataclasses.field(default_factory=list)   # (t_a, t_b, plen)
    gaps: list = dataclasses.field(default_factory=list)     # s, inside window
    ctx: list = dataclasses.field(default_factory=list)      # decode positions
    tokens: int = 0                                          # inside window
    ttft: list = dataclasses.field(default_factory=list)     # open loop, s
    lateness: list = dataclasses.field(default_factory=list)
    attempted: int = 0
    missed: int = 0
    finished: dict = dataclasses.field(default_factory=dict)  # uid → (slot, prompt)


class Driver:
    def __init__(self, mixer, stream, request_type, annotate: bool = False):
        self.mx = mixer
        self.stream = stream
        self.Req = request_type
        self.annotate = annotate
        self.live: dict[int, list] = {}    # slot → [uid, t_last, prompt]
        self.rec = Record()

    # -- one admission / one step, timed ---------------------------------
    def _span(self, name: str, **stats):
        if not self.annotate:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name, **stats)

    def _admit(self, req, due: float | None = None) -> None:
        mx, rec = self.mx, self.rec
        t_a = time.perf_counter()
        with self._span("bench.admit", plen=len(req.prompt)):
            slot = mx.admit(self.Req(uid=req.uid, prompt=req.prompt,
                                     max_new=req.max_new))
        t_b = time.perf_counter()
        rec.admits.append((t_a, t_b, len(req.prompt)))
        if rec.t0 <= t_b <= rec.t_end:
            rec.tokens += 1
        if due is not None:
            rec.ttft.append(t_b - due)
        if mx.active[slot]:
            self.live[slot] = [req.uid, t_b, req.prompt]
        else:
            rec.finished[req.uid] = (slot, req.prompt)

    def _step(self) -> None:
        mx, rec = self.mx, self.rec
        before = [int(s) for s in np.nonzero(mx.active)[0]]
        pos = mx.pos[before].copy()
        t_a = time.perf_counter()
        with self._span("bench.step"):
            mx._step()
        t_b = time.perf_counter()
        rec.steps.append((t_a, t_b, len(before)))
        inside = rec.t0 <= t_b <= rec.t_end
        if inside:
            rec.ctx.extend((pos + 1).tolist())
        for slot in before:
            entry = self.live[slot]
            if inside:
                rec.gaps.append(t_b - entry[1])
                rec.tokens += 1
            entry[1] = t_b
            req = mx._req[slot]
            if req is None or req.uid != entry[0]:
                rec.finished[entry[0]] = (slot, entry[2])
                del self.live[slot]

    # -- set-up ------------------------------------------------------------
    def warm(self, lengths, fill_closed: bool, rng: np.random.Generator,
             steps: int = 8) -> None:
        """Compile every prompt length of the mix and the decode step; for
        a closed loop, then fill every slot with requests of residual
        budgets (a uniform share of each drawn output) and step, so the
        window starts with all slots busy at mixed ages."""
        mx = self.mx
        for i, plen in enumerate(lengths):
            mx.admit(self.Req(uid=f"warm{i}", max_new=1,
                              prompt=np.zeros(plen, np.int32)))
        mx.admit(self.Req(uid="warm-step", max_new=steps + 1,
                          prompt=np.zeros(lengths[0], np.int32)))
        for _ in range(steps):
            mx._step()
        if not fill_closed:
            return
        self.rec.t0 = self.rec.t_end = float("inf")
        while not mx.active.all():
            req = next(self.stream)
            budget = max(1, int(np.ceil(rng.uniform() * req.max_new)))
            self._admit(dataclasses.replace(req, max_new=budget))
        for _ in range(steps):
            self._refill()
            self._step()

    def _refill(self) -> None:
        while not self.mx.active.all():
            self._admit(next(self.stream))

    # -- the window ----------------------------------------------------------
    def run(self, seconds: float) -> Record:
        rec = self.rec
        rec.finished.clear()
        rec.t0 = time.perf_counter()
        rec.t_end = rec.t0 + seconds
        if self.stream.mix["loop"] == "closed":
            self._closed()
        else:
            self._open()
        return rec

    def _closed(self) -> None:
        rec = self.rec
        live, n0 = len(self.live), len(rec.admits)
        while time.perf_counter() < rec.t_end:
            self._refill()
            self._step()
        rec.attempted = live + len(rec.admits) - n0

    def _open(self) -> None:
        rec, mx = self.rec, self.mx
        queue: deque = deque()
        nxt = next(self.stream)
        while True:
            now = time.perf_counter()
            while rec.t0 + nxt.due_s <= min(now, rec.t_end):
                queue.append(nxt)
                rec.lateness.append(now - rec.t0 - nxt.due_s)
                rec.attempted += 1
                nxt = next(self.stream)
            if now >= rec.t_end and (not queue or now >= rec.t_end + DRAIN_S):
                break
            while queue and not mx.active.all():
                req = queue.popleft()
                self._admit(req, due=rec.t0 + req.due_s)
            if mx.active.any():
                self._step()
            elif not queue and now < rec.t_end:
                time.sleep(max(0.0, min(rec.t0 + nxt.due_s, rec.t_end)
                               - time.perf_counter()))
        # a miss: its time to first token is at least the wait so far
        rec.missed = len(queue)
        rec.ttft += [now - rec.t0 - req.due_s for req in queue]
