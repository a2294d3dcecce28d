#!/usr/bin/env python3
"""Split a cell's traced decode steps by the program's own names.

  python3 benchmarks/chip/split.py --workload <cell> --seed <n> \\
      [--seconds 10] [--out DIR]

Sets the cell up as ``run.py`` does and profiles a window of ``--seconds``
with the harness's ``bench.*`` annotations and the program's spans on the
profiler's clock.  A closed-loop cell then profiles a second window with
the program's spans off (``repro.obs.trace`` sees no recording profiler),
so the two compare what the program's annotations cost.  The first window
is reduced by ``tracing.py`` (the benchmark's own readings) and by
``scopes.py`` (device time by name scope, device idle by host phase); op
scopes come from the compiled decode step's HLO text, taken after the
windows.  Not part of a benchmark run: it checks nothing against the
reference.

The last line of standard output is one JSON object; the decode step's
HLO text is written to ``--out`` (default ``.bench_split`` in the
checkout).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run  # noqa: E402
import scopes  # noqa: E402
import spec  # noqa: E402
import tracing  # noqa: E402


def profile(win, seconds: float, trace_dir: str):
    """One profiled window; the trace's path and the steps' host gaps."""
    import jax
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    n0 = len(win.rec.steps)
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    win.run(seconds)
    jax.profiler.stop_trace()
    gaps = [b - a for a, b, _ in win.rec.steps[n0:]]
    return glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[0], gaps


def window(tr, gaps) -> dict:
    n = max(len(tr.steps), 1)
    return {"steps": len(tr.steps),
            "step_gap_ms_mean": 1e3 * sum(gaps) / max(len(gaps), 1),
            "device_ms": 1e-6 * sum(s.busy_ns for s in tr.steps) / n,
            "idle_in_step_ms": 1e-6 * sum(s.end - s.start - s.busy_ns
                                          for s in tr.steps) / n,
            "idle_share": 1.0 - tr.busy_s / tr.window_s}


def step_hlo(mx) -> str:
    """The compiled decode step's HLO text, for the mixer's live shapes."""
    import jax.numpy as jnp
    fn = mx._step_fn
    return fn.func.lower(mx.params, mx.cache,
                         jnp.asarray(mx.pending, jnp.int32),
                         jnp.asarray(mx.pos, jnp.int32),
                         **fn.keywords).compile().as_text()


def main(argv=None, *, root: str = spec.ROOT, here: str = spec.HERE,
         bench_root: str | None = None, require_chip: bool = True,
         cache: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=run.TRACE_S)
    ap.add_argument("--out", default=os.path.join(root, ".bench_split"))
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(root, "src"))
    c = spec.cell(spec.benchmark(bench_root or root), args.workload, here)
    from repro.obs import trace as otr
    chips = c["workload"]["chips"]
    device = run.device_info(chips, require_chip)
    if cache:
        run.configure_cache(root)
    st = run.setup(c, args.seed, annotate=True)
    win = st["driver"]
    # scopes read per step: attention, the KV write, the head, and each
    # projection role of the architecture
    step_scopes = scopes.SCOPES + ("head",) + tuple(st["roles"])
    out = {"workload": args.workload, "seed": args.seed, "device": device}

    trace_dir = os.path.join(root, ".bench_trace")
    kept = os.path.join(root, ".bench_split.xplane.pb")
    path, gaps = profile(win, args.seconds, trace_dir)
    shutil.copy(path, kept)
    on = tracing.reduce(kept, chips)
    out["on"] = window(on, gaps)
    if c["mix"]["loop"] == "closed":
        class Off:                      # the program sees no profiler
            @staticmethod
            def is_enabled():
                return False
        recording, otr.TraceAnnotation = otr.TraceAnnotation, Off
        try:
            path, gaps = profile(win, args.seconds, trace_dir)
        finally:
            otr.TraceAnnotation = recording
        out["off"] = window(tracing.reduce(path, chips), gaps)
    shutil.rmtree(trace_dir, ignore_errors=True)

    hlo = step_hlo(win.mx)
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, f"{args.workload}.hlo.txt"), "w") as f:
        f.write(hlo)
    try:
        sp = scopes.reduce(kept, hlo, chips, scopes=step_scopes)
    finally:
        os.remove(kept)
    out.update(scope_ms=sp.scope_ms, idle_ms=sp.idle_ms,
               sync_idle_ms=sp.sync_idle_ms, host_idle_ms=sp.host_idle_ms,
               unscoped_ops=sp.unscoped, traced_steps=sp.steps)
    names, fused = scopes.hlo_op_names(hlo), scopes.fusion_scopes(hlo)
    out["top_ops"] = []
    for op, secs in on.breakdown["device_ops"]:
        instr = op.split(" ", 1)[0]
        held = sorted({next((s for s in step_scopes
                             if scopes.has_scope(p, s)), "-")
                       for p in fused.get(instr, ())})
        out["top_ops"].append({"op": op, "s": secs,
                               "op_name": names.get(instr),
                               "scopes_inside": held})
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
