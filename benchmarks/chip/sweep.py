#!/usr/bin/env python3
"""Find the highest rate an open-loop cell sustains: one set-up, then a
window at each rate, the mixer drained between windows.

  python3 benchmarks/chip/sweep.py --workload chatglm3-6b.chat \\
      --seed 5 --seconds 20 --rates 4,6,8,10,12

Prints one JSON line per rate: requests due, time to first token (median,
p95), the queue left when the window closed, and decode steps per second.
A rate is sustained while the queue at the close stays near empty and the
p95 stays within a few admissions; past the knee the queue grows all
through the window.  Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run  # noqa: E402
import spec  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()

    sys.path.insert(0, os.path.join(spec.ROOT, "src"))
    c = spec.cell(spec.benchmark(), args.workload)
    run.device_info(c["workload"]["chips"], require_chip=True)
    run.configure_cache(spec.ROOT)
    st = run.setup(c, args.seed, annotate=False)
    mx = st["driver"].mx
    import traffic
    from repro.launch.mixer import Request
    from window import Driver
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        mix = dict(c["mix"], rate_per_s=rate)
        stream = traffic.Stream(mix, args.seed + i, st["dims"]["vocab"])
        d = Driver(mx, stream, Request)
        rec = d.run(args.seconds)
        queued = rec.attempted - sum(
            1 for a, _, _ in rec.admits if a < rec.t_end)
        while mx.active.any():              # drain before the next rate
            mx._step()
        mx.results.clear()                  # the next stream reuses uids
        w = rec.t_end - rec.t0
        print(json.dumps({
            "rate_per_s": rate, "due": rec.attempted,
            "queued_at_close": queued, "missed": rec.missed,
            "ttft_p50_ms": 1e3 * run.quantile(rec.ttft, 0.5),
            "ttft_p95_ms": 1e3 * run.quantile(rec.ttft, 0.95),
            "itl_p50_ms": 1e3 * run.quantile(rec.gaps, 0.5),
            "itl_p95_ms": 1e3 * run.quantile(rec.gaps, 0.95),
            "steps_per_s": sum(1 for a, _, _ in rec.steps if a < rec.t_end)
            / w,
            "admit_ms": 1e3 * sum(b - a for a, b, _ in rec.admits)
            / max(len(rec.admits), 1)}), flush=True)
        time.sleep(0.5)
    return 0


if __name__ == "__main__":
    sys.exit(main())
