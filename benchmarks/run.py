"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Roofline terms come from the
dry-run artifacts (benchmarks/roofline.py builds the table; run
``python -m repro.launch.dryrun --all`` first for that one).

``--quick`` runs a smoke pass (tiny model, one arch, reduced iterations)
through every suite whose ``run`` accepts a ``quick`` flag and skips the
rest — exercised by a tier-1 test so the benchmark drivers can't silently
rot.  ``--json PATH`` additionally writes every emitted row plus per-suite
wall-clocks to PATH as JSON; the convention across PRs is ``BENCH_<n>.json``
(n = PR number), so the perf trajectory stays machine-readable.
``--memo PATH`` loads a durable memo snapshot before the suites run and
saves the (grown) caches back afterwards — repeat runs replay the searches
they already paid for; a stale snapshot (different code) is ignored.
``python benchmarks/run.py [suite-substring] [--quick] [--json PATH]
[--memo PATH]``.
"""

from __future__ import annotations

import inspect
import json
import os
import sys
import time
import traceback

# direct `python benchmarks/run.py` bootstraps its own import roots (pytest
# gets the same paths from pytest.ini's `pythonpath = src .`)
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def main(argv=None) -> int:
    from benchmarks import (bench_dimo, bench_energy_validation, bench_exec,
                            bench_fig5_payload, bench_fig6_penalty,
                            bench_format_opt, bench_formats_feasibility,
                            bench_kernels, bench_multimodel, bench_serve,
                            bench_speed, common)
    suites = [
        ("fig5", bench_fig5_payload.run),
        ("fig6", bench_fig6_penalty.run),
        ("fig8/9", bench_energy_validation.run),
        ("fig10", bench_format_opt.run),
        ("fig11", bench_multimodel.run),
        ("tableI", bench_speed.run),
        ("dimo", bench_dimo.run),
        ("feasibility", bench_formats_feasibility.run),
        ("kernels", bench_kernels.run),
        ("exec", bench_exec.run),
        ("serve", bench_serve.run),
    ]
    argv = list(sys.argv[1:] if argv is None else argv)
    quick = "--quick" in argv
    argv = [a for a in argv if a != "--quick"]
    json_path = None
    if "--json" in argv:
        k = argv.index("--json")
        if k + 1 >= len(argv):
            print("error: --json requires a PATH", file=sys.stderr)
            return 1
        json_path = argv[k + 1]
        del argv[k:k + 2]
    memo_path = None
    if "--memo" in argv:
        k = argv.index("--memo")
        if k + 1 >= len(argv):
            print("error: --memo requires a PATH", file=sys.stderr)
            return 1
        memo_path = argv[k + 1]
        del argv[k:k + 2]
    only = argv[0] if argv else None
    if memo_path is not None:
        from repro.core import memo
        if os.path.exists(memo_path):
            loaded = memo.load(memo_path)
            print(f"# memo snapshot {memo_path}: "
                  f"{'loaded' if loaded else 'stale, ignored'}", flush=True)
    rows: list = []
    suite_s: dict[str, float] = {}
    if json_path is not None:
        common.set_collector(rows)
    print("name,us_per_call,derived")
    failures = 0
    try:
        for name, fn in suites:
            if only and only not in name:
                continue
            kwargs = {}
            if quick:
                if "quick" not in inspect.signature(fn).parameters:
                    print(f"# suite {name} skipped (no quick mode)",
                          flush=True)
                    continue
                kwargs["quick"] = True
            t0 = time.perf_counter()
            try:
                fn(**kwargs)
            except Exception:
                failures += 1
                common.emit(name, 0.0, "FAILED")   # mirrored into --json
                traceback.print_exc()
            suite_s[name] = time.perf_counter() - t0
            print(f"# suite {name} done in {suite_s[name]:.1f}s", flush=True)
        # whole-run cache telemetry (repro.obs sources): the memo and
        # kernel-cache counters accumulated ACROSS the suites that ran —
        # the `_run` suffix keeps these distinct from per-suite
        # `memo_stats_*` rows some suites emit themselves
        from repro.core import memo
        from repro.kernels import ops as kops
        for cname, st in sorted(memo.stats().items()):
            if st.lookups:
                common.emit(f"memo_stats_run_{cname}", 0.0,
                            f"hits={st.hits} misses={st.misses} "
                            f"hit_rate={st.hit_rate:.3f}")
        kc = kops.kernel_cache_stats()
        common.emit("kernel_cache_run", 0.0,
                    f"hits={kc['hits']} misses={kc['misses']} "
                    f"entries={kc['entries']}")
    finally:
        if json_path is not None:
            common.set_collector(None)
            with open(json_path, "w") as f:
                json.dump({"rows": rows, "suite_s": suite_s,
                           "quick": quick, "failures": failures},
                          f, indent=1)
            print(f"# wrote {len(rows)} rows to {json_path}", flush=True)
        if memo_path is not None:
            from repro.core import memo
            n = memo.save(memo_path)
            print(f"# memo snapshot {memo_path}: saved {n} entries",
                  flush=True)
    return failures


if __name__ == "__main__":
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    raise SystemExit(1 if main() else 0)
