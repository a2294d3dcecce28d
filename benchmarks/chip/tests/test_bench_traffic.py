"""The seeded request streams and the open-loop clock of the window."""

import time

import numpy as np
import pytest

import spec
import traffic
from window import Driver

SEED = 2**31 + 12345


@pytest.mark.parametrize("mix_name", ["decode", "chat"])
def test_same_seed_same_schedule(mix_name):
    mix = spec.traffic(mix_name)
    a, b = traffic.Stream(mix, SEED, 65024), traffic.Stream(mix, SEED, 65024)
    for _ in range(50):
        ra, rb = next(a), next(b)
        assert (ra.uid, ra.max_new, ra.due_s) == (rb.uid, rb.max_new, rb.due_s)
        assert np.array_equal(ra.prompt, rb.prompt)


@pytest.mark.parametrize("mix_name", ["decode", "chat"])
def test_lengths_within_bounds_and_rounded(mix_name):
    mix = spec.traffic(mix_name)
    s = traffic.Stream(mix, SEED, 65024)
    reqs = [next(s) for _ in range(mix["pool"])]
    plens = np.array([len(r.prompt) for r in reqs])
    outs = np.array([r.max_new for r in reqs])
    p, o = mix["prompt"], mix["output"]
    assert plens.min() >= p["min"] and plens.max() <= p["max"]
    assert (plens % p["round_to"] == 0).all()
    assert set(plens) <= set(traffic.prompt_lengths(mix))
    assert outs.min() >= o["min"] and outs.max() <= o["max"]
    assert (plens + outs <= mix["max_len"]).all()
    assert all(r.prompt.max() < 65024 for r in reqs)


@pytest.mark.parametrize("mix_name", ["decode", "chat"])
def test_seeds_permute_one_pool(mix_name):
    """Two seeds offer the same sizes and arrival gaps, in another order."""
    mix = spec.traffic(mix_name)
    n = mix["pool"]
    sa, sb = traffic.Stream(mix, 1, 512), traffic.Stream(mix, SEED, 512)
    ra = [next(sa) for _ in range(n)]
    rb = [next(sb) for _ in range(n)]
    key = lambda rs: sorted((len(r.prompt), r.max_new) for r in rs)
    assert key(ra) == key(rb)
    assert [len(r.prompt) for r in ra] != [len(r.prompt) for r in rb]
    if mix["loop"] == "open":
        assert ra[-1].due_s == pytest.approx(rb[-1].due_s)
        gaps = np.diff([0.0] + [r.due_s for r in ra])
        assert np.mean(gaps) == pytest.approx(1 / mix["rate_per_s"], rel=0.2)
        assert np.std(gaps) / np.mean(gaps) == pytest.approx(
            mix["arrival_cv"], rel=0.25)


class _SlowMixer:
    """One slot; admission takes ``admit_s`` and emits one token, so every
    request after the first waits for the one before it."""

    def __init__(self, admit_s):
        self.admit_s = admit_s
        self.active = np.zeros(1, bool)
        self.pos = np.zeros(1, np.int64)
        self._req = [None]
        self.results = {}

    def admit(self, req):
        time.sleep(self.admit_s)
        return 0                    # max_new 1: done at admission

    def _step(self):
        raise AssertionError("no request outlives its admission here")


def test_ttft_counts_from_due_time_not_send_time():
    mix = {"loop": "open", "rate_per_s": 1000.0, "arrival_cv": 1.0,
           "slots": 1, "max_len": 64, "pool": 8, "base_seed": 0,
           "prompt": {"median": 4, "sigma": 0.0, "min": 4, "max": 4},
           "output": {"median": 1, "sigma": 0.0, "min": 1, "max": 1}}
    stream = traffic.Stream(mix, SEED, 16)
    mx = _SlowMixer(admit_s=0.02)
    d = Driver(mx, stream, lambda **kw: kw)
    rec = d.run(0.05)
    # arrivals every ~1 ms, admissions 20 ms apart: the k-th request waits
    # for the k-1 before it, which its time from sending alone would hide
    assert rec.attempted >= 20 and rec.missed == 0
    assert len(rec.ttft) == rec.attempted
    ttft = np.array(rec.ttft)
    send_to_token = np.array([b - a for a, b, _ in rec.admits])
    assert (ttft >= send_to_token - 1e-6).all()
    assert ttft.max() > 5 * np.median(send_to_token)
    assert (np.diff(ttft[:10]) > 0.01).all()
