"""Seeded request streams, read from a traffic mix's parameters.

One generator serves every mix (``traffic/<mix>.json``):

* ``loop``: ``"closed"`` — one client per slot, each sending its next
  request the moment its last one finishes (offline and batch generation);
  ``"open"`` — requests due on a schedule whatever the server does
  (independent users), with Gamma inter-arrival gaps of mean
  ``1 / rate_per_s`` and coefficient of variation ``arrival_cv``.
* ``prompt`` / ``output``: lognormal lengths given by ``median`` and
  ``sigma``, clipped to ``[min, max]``; a prompt length is then rounded up
  to a multiple of ``round_to``.

The work is the same for every seed.  A pool of ``pool`` requests (lengths
and gaps) is drawn once from the mix's own ``base_seed``; the run's seed
only permutes the pool's order and draws the prompts' token ids.  So two
seeds differ in arrangement, not in the sizes and arrivals they offer.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np


@dataclasses.dataclass(frozen=True)
class Request:
    uid: str
    prompt: np.ndarray         # (plen,) int32 token ids
    max_new: int
    due_s: float               # due time from the window's start (open loop)


def _lengths(rng: np.random.Generator, spec: dict, n: int) -> np.ndarray:
    raw = np.exp(rng.normal(math.log(spec["median"]), spec["sigma"], n))
    out = np.clip(np.ceil(raw), spec["min"], spec["max"]).astype(np.int64)
    step = spec.get("round_to", 1)
    out = -(-out // step) * step
    if out.max() > spec["max"]:
        raise ValueError(f"max {spec['max']} is not a multiple of round_to "
                         f"{step}")
    return out


def prompt_lengths(mix: dict) -> list[int]:
    """Every prompt length the mix can draw, ascending: the shapes set-up
    has to warm."""
    p = mix["prompt"]
    step = p.get("round_to", 1)
    lo = -(-p["min"] // step) * step
    return list(range(lo, p["max"] + 1, step))


class Stream:
    """The seeded request stream of one run."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        self.mix = mix
        self.vocab = vocab
        n = mix["pool"]
        base = np.random.default_rng(mix["base_seed"])
        self._plen = _lengths(base, mix["prompt"], n)
        self._out = _lengths(base, mix["output"], n)
        if mix["loop"] == "open":
            cv = mix["arrival_cv"]
            shape = 1.0 / cv ** 2
            self._gap = base.gamma(shape, 1.0 / (mix["rate_per_s"] * shape), n)
        elif mix["loop"] != "closed":
            raise ValueError(f"unknown loop {mix['loop']!r}")
        rng = np.random.default_rng(seed)
        self._order = rng.permutation(n)
        self._tok_rng = np.random.default_rng([seed, 1])
        self._next = 0
        self._due = 0.0

    def __next__(self) -> Request:
        i = self._next
        j = self._order[i % len(self._order)]
        self._next += 1
        if self.mix["loop"] == "open":
            self._due += float(self._gap[j])
        plen, out = int(self._plen[j]), int(self._out[j])
        prompt = self._tok_rng.integers(0, self.vocab, plen, dtype=np.int32)
        return Request(uid=f"q{i}", prompt=prompt, max_new=out,
                       due_s=self._due)
