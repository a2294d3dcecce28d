#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

  python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
      --seconds <s> --trace <0|1>

Set-up goes through the program's public path: plan
(``repro.exec.build_exec_plan``), the benchmark's seeded weights (drawn by
the configuration's architecture, ``arch/<name>.py``), prune and
compress (``prune_params``, ``compress_params``), ``CompressedModel``, and a
``repro.launch.mixer.Mixer``; every prompt length of the mix and the decode
step are warmed.  The window then drives the cell's traffic through the
mixer for ``--seconds`` (``window.py``).  With ``--trace 1`` the window is
profiled (at most ``TRACE_S`` of it) and the cell's per-layer metrics are
reported instead of its end-to-end ones.  After the window the served
tokens are checked against the float32 reference (``check.py``).
``--control bf16,fp8`` puts those controls of the reference in the
program's place for the check instead (not part of a benchmark run): the
line's ``correct`` then says whether any control passed.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` with
``--trace 1``), with ``checks`` last: each number compared beside its
limit, which are also the last lines on standard error.  Exits non-zero,
printing no result, when JAX finds no TPU or fewer chips than the cell
asks for.
"""

from __future__ import annotations

import argparse
import gc
import glob
import json
import os
import shutil
import statistics
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import spec  # noqa: E402

#: Longest stretch of a ``--trace 1`` window that is profiled.
TRACE_S = 10.0


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def fail(msg: str) -> None:
    log(f"run: FAIL: {msg}")
    raise SystemExit(1)


class CompileClock:
    """Seconds JAX spends turning Python into device programs (tracing,
    lowering, backend compiles and persistent-cache reads), and the
    backend compiles among them."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event in self.EVENTS:
            self.seconds += secs
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def device_info(chips: int, require_chip: bool) -> dict:
    """The devices as JAX reports them; exits unless they are TPUs and at
    least ``chips`` of them."""
    import jax
    devices = jax.devices()
    dev = devices[0]
    if require_chip and dev.platform != "tpu":
        fail(f"JAX found no TPU (first device on {dev.platform!r}); the "
             f"benchmark measures the chip and does not fall back")
    if len(devices) < chips:
        fail(f"the cell asks for {chips} chips; JAX sees {len(devices)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": chips}


def configure_cache(root: str) -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    caching every program, so that only a checkout's first run compiles."""
    import jax
    path = os.path.join(root, ".jax_cache")
    os.makedirs(path, exist_ok=True)       # JAX does not make it
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # no eviction: an evicting cache lost its entries on the chip's hosts
    jax.config.update("jax_compilation_cache_max_size", -1)
    return path


def build_plan(pcfg, cfg: dict):
    from repro.core.cosearch import CoSearchConfig
    from repro.core.engine import EngineConfig
    from repro.core.sparsity import BlockBernoulli
    from repro.exec import build_exec_plan
    p, sp = cfg["plan"], cfg["sparsity"]
    scfg = CoSearchConfig(
        objective=p["objective"],
        engine=EngineConfig(max_levels=p["max_levels"],
                            max_allocs_per_pattern=p["max_allocs_per_pattern"]),
        spatial_top=p["spatial_top"], max_pairs=p["max_pairs"])
    return build_exec_plan(
        pcfg, BlockBernoulli(sp["density"], sp["block"][0] * sp["block"][1]),
        tokens=p["tokens"], search_cfg=scfg, value_bits=p["value_bits"])


def check_tree(tree, pcfg) -> None:
    """The benchmark's weights have the program's layout, shapes and
    dtypes."""
    import jax
    from repro.models.transformer import Model
    want = jax.eval_shape(Model(pcfg).init, jax.random.key(0))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
    if jax.tree.structure(want) != jax.tree.structure(got) or \
            jax.tree.leaves(want) != jax.tree.leaves(got):
        fail(f"the program's parameter layout {want} differs from the "
             f"benchmark's weights {got}")


def quantile(xs, q: float) -> float:
    """The ``q`` quantile (0 < q < 1) by linear interpolation."""
    import numpy as np
    return float(np.quantile(np.asarray(xs, float), q))


def setup(c: dict, seed: int, annotate: bool) -> dict:
    """Plan, weights, prune, compress, mixer, warm-up.  Returns the state
    the window and the metrics need."""
    import jax
    import numpy as np
    from repro.exec import CompressedModel, compress_params, prune_params
    from repro.launch.mixer import Mixer, Request
    from repro.models.transformer import Model

    import traffic
    import weights
    from window import Driver

    cfg, mix, arch = c["config"], c["mix"], c["arch"]
    dims = arch.dims(cfg)
    density = cfg["sparsity"]["density"]
    pcfg = arch.program_config(cfg, dims)
    t = time.perf_counter()
    plan = build_plan(pcfg, cfg)
    plan_s = time.perf_counter() - t
    roles = arch.roles(dims)
    masks = weights.masks(cfg, roles)
    w = arch.make(seed, dims, masks, density)
    tree = arch.program_tree(w)
    del w
    check_tree(tree, pcfg)
    pruned = prune_params(tree, plan, pcfg)
    del tree                                   # the unpruned projections
    store = compress_params(pruned, plan, pcfg)
    cm = CompressedModel(Model(pcfg), store)
    jax.block_until_ready(jax.tree.leaves(cm.extras))
    stats = jax.devices()[0].memory_stats() or {}
    log(f"set-up: plan {plan_s:.2f}s; bytes_in_use after compress "
        f"{stats.get('bytes_in_use')}")
    for op in plan.ops:
        ch = op.choice
        log(f"  plan {op.role:<11} {op.n}x{op.k} kernel={ch.kind} "
            f"block={ch.block_n}x{ch.block_k} format={ch.format_str}")
    nnz = weights.nnz_per_layer(roles, masks, density)
    short = 0.0
    for role, sr in cm.stacked.roles.items():
        want = nnz[role] * pcfg.n_layers
        short = max(short, (want - sr.payload_elems) / want)
    dtype = served_dtype(cm.stacked, pruned)
    log(f"  served weights in {dtype}")
    stream = traffic.Stream(mix, seed, dims["vocab"])
    mx = Mixer(cm, pruned, slots=mix["slots"], max_len=mix["max_len"])
    driver = Driver(mx, stream, Request, annotate=annotate)
    driver.warm(traffic.prompt_lengths(mix), mix["loop"] == "closed",
                np.random.default_rng([seed, 3]))
    return {"dims": dims, "plan_s": plan_s, "roles": roles, "masks": masks,
            "density": density, "driver": driver, "nnz_short": short,
            "payload_dtype": dtype,
            "nnzb": weights.nnz_blocks(roles, masks, density),
            "nnz_layer": sum(nnz.values())}


def served_dtype(stacked, params) -> str:
    """The dtype each projection weight is served in (``+``-joined where
    they differ): a kernel role's payload, or the dense weight itself."""
    got = set()
    for role, sr in stacked.roles.items():
        if sr.data is None:
            group, leaf = role.split(".", 1)
            got.add(str(params["blocks"][group][leaf].dtype))
        else:
            got.add(str(sr.data.get("blocks", sr.data.get("values")).dtype))
    return "+".join(sorted(got))


def end_to_end(rec, names: list[str]) -> dict:
    out = {}
    window = rec.t_end - rec.t0
    for name in names:
        if name == "output_tok_per_s":
            out[name] = (rec.tokens / window, "tokens/s")
        elif name.startswith("itl_p") and name.endswith("_ms"):
            # a percentile of the gaps between a request's tokens, by name
            q = float(name[len("itl_p"):-len("_ms")]) / 100
            out[name] = (1e3 * quantile(rec.gaps, q), "ms")
    return out


def main(argv=None, *, root: str = spec.ROOT, here: str = spec.HERE,
         bench_root: str | None = None, require_chip: bool = True,
         cache: bool = True, sabotage=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", default="",
                    help="comma-separated controls of the configuration "
                         "(bf16, fp8) to check in the program's place; not "
                         "part of a benchmark run")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(root, "src", "repro")):
        fail(f"the program (src/repro) is not in {root}")
    sys.path.insert(0, os.path.join(root, "src"))
    try:
        c = spec.cell(spec.benchmark(bench_root or root), args.workload, here)
    except (ValueError, OSError) as e:
        fail(str(e))
    controls = [x for x in args.control.split(",") if x]
    unknown = set(controls) - set(c["config"]["precision"]["controls"])
    if unknown:
        fail(f"no control {sorted(unknown)} in the configuration")
    import jax
    device = device_info(c["workload"]["chips"], require_chip)
    log(f"device: {device['kind']} x{device['count']} ({device['platform']}), "
        f"jax {jax.__version__}; compile cache "
        f"{configure_cache(root) if cache else 'off'}")
    clock = CompileClock()

    st = setup(c, args.seed, annotate=bool(args.trace))
    driver = st["driver"]
    if sabotage is not None:
        sabotage(driver)
    setup_s = time.perf_counter() - t_start
    setup_compile_s, compiles0 = clock.seconds, clock.compiles
    log(f"set-up: {setup_s:.2f}s, of it {setup_compile_s:.2f}s making "
        f"programs ({clock.compiles} backend compiles, {clock.cache_hits} "
        f"persistent-cache hits)")

    seconds = min(args.seconds, TRACE_S) if args.trace else args.seconds
    trace_dir = os.path.join(root, ".bench_trace")
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0          # the harness's annotations only
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    rec = driver.run(seconds)
    if args.trace:
        jax.profiler.stop_trace()
    in_window = clock.compiles - compiles0
    mx = driver.mx
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.devices()[: device["count"]])
    device["memory_peak_bytes"] = int(peak)
    log(f"window: {rec.t_end - rec.t0:.2f}s, {len(rec.steps)} steps, "
        f"{len(rec.admits)} admissions, {rec.tokens} tokens, "
        f"{rec.attempted} requests attempted, {rec.missed} missed; "
        f"backend compiles inside the window: {in_window}")
    if rec.ttft:
        # printed, not bounded: at this load the tail swings with where the
        # bursts fall (PERF.md)
        log(f"  time to first token from due time over {len(rec.ttft)} "
            f"requests: median {1e3 * quantile(rec.ttft, 0.5):.1f} ms, p95 "
            f"{1e3 * quantile(rec.ttft, 0.95):.1f} ms")
    if rec.lateness:
        log(f"  open-loop generator lateness (arrival to enqueue, waits "
            f"for the step in flight): median "
            f"{1e3 * statistics.median(rec.lateness):.3f} ms, max "
            f"{1e3 * max(rec.lateness):.3f} ms")
    log(f"peak_bytes_in_use: {peak}")

    out = {"correct": False, "attempted": rec.attempted,
           "failed": rec.missed}
    if args.trace:
        import tracing
        paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
        tr = tracing.reduce(paths[0], device["count"])
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = tr.busy_s
        device["window_s"] = tr.window_s
        import counts
        roles = counts.served_kernel_roles(mx.model.stacked, st["nnzb"])
        ctx = {"rec": rec, "trace": tr, "dims": st["dims"],
               "plan_s": st["plan_s"], "setup_compile_s": setup_compile_s,
               "peak": counts.peaks(device["kind"]),
               "kernel_roles": roles, "n_layers": st["dims"]["n_layers"],
               "nnz_layer": st["nnz_layer"],
               "kv_bytes_per_position": counts.kv_bytes_per_position(
                   mx.cache)}
        # the architecture counts the work from what was served; the
        # served arrays stay out of ``ctx``, which outlives the program
        served = dict(ctx, stacked=mx.model.stacked, params=mx.params)
        ctx["weight_bytes"] = c["arch"].step_weight_bytes(served)
        ctx["decode_flops"] = c["arch"].decode_flops(served)
        ctx["decode_kv_bytes"] = c["arch"].decode_kv_bytes(served)
        del served
        kernels = sorted(sp.kernels for sp in tr.steps) or [0]
        log(f"trace: {tr.window_s:.2f}s, {len(tr.steps)} steps, "
            f"{len(tr.admits)} admissions; Mosaic kernel events per step "
            f"min/median/max {kernels[0]}/{kernels[len(kernels) // 2]}/"
            f"{kernels[-1]} ({len(ctx['kernel_roles'])} kernel roles x "
            f"{ctx['n_layers']} layers); kernel time per step "
            f"{1e-6 * sum(sp.kernel_ns for sp in tr.steps) / max(len(tr.steps), 1):.3f} ms")
        metrics = {}
        for m in c["per_layer"]:
            v = spec.metric_reader(m["name"], here)(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        out["breakdown"] = tr.breakdown
    else:
        names = [m["name"] for m in c["end_to_end"]]
        got = end_to_end(rec, names)
        got["setup_s"] = (setup_s, "s")
        metrics = {n: {"value": v, "unit": u} for n, (v, u) in got.items()}
    out["metrics"] = metrics
    out["device"] = device

    # the served tokens against the reference, once the program is freed
    results = mx.results
    finished = dict(rec.finished)
    del mx, driver, st["driver"]
    gc.collect()
    import check
    arch = c["arch"]
    samples = check.sample(finished, results, args.seed)
    w = arch.make(args.seed, st["dims"], st["masks"], st["density"])
    t = time.perf_counter()
    g = check.gaps(arch.logits, w, samples, st["dims"], c["mix"]["max_len"],
                   controls)
    log(f"reference: {len(samples)} requests from {g['slots']} slots, "
        f"{g['tokens']} served tokens, {time.perf_counter() - t:.2f}s")
    precision, limits = c["config"]["precision"], c["config"]["limits"]

    def checks_of(gap: float, dtype: str) -> dict:
        return {"logit_gap": {"value": gap, "limit": limits["logit_gap"]},
                "payload_dtype": {"value": dtype,
                                  "limit": precision["weights"]},
                "nnz_short": {"value": st["nnz_short"], "limit": 0.0},
                "missed": {"value": rec.missed, "limit": 0},
                "slots_checked": {"value": g["slots"],
                                  "limit": check.SAMPLE_SLOTS}}
    checks = checks_of(g["logit_gap"], st["payload_dtype"])
    out["correct"] = check.judge(checks)
    if controls:
        for name, v in checks.items():
            log(f"program: check {name} = {v['value']!r} (limit "
                f"{v['limit']!r})")
        log(f"program: correct {out['correct']}")
        checks, verdicts = {}, []
        for name in controls:
            ck = checks_of(g["controls"][name], arch.CONTROL_DTYPES[name])
            verdicts.append(check.judge(ck))
            log(f"control {name}: correct {verdicts[-1]}")
            checks.update({f"{name}.{k}": v for k, v in ck.items()})
        out["correct"] = any(verdicts)
    out["checks"] = checks
    for name, v in checks.items():
        log(f"check {name} = {v['value']!r} (limit {v['limit']!r})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
