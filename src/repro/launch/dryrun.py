import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture × input-shape ×
mesh) cell against the production mesh, with NO real allocation
(ShapeDtypeStruct stand-ins everywhere).

The two lines above MUST precede every other import — jax locks the device
count at first init.  Do not set that flag globally: smoke tests and benches
must see 1 device.

Per cell this script records:
  * compiled.memory_analysis()  — proves the cell fits per-device HBM;
  * compiled.cost_analysis()    — HLO FLOPs / bytes for §Roofline;
  * collective operand bytes parsed from the compiled HLO text
    (all-gather / all-reduce / reduce-scatter / all-to-all /
    collective-permute) — cost_analysis does not report these;
  * lower/compile wall times.

Artifacts land in artifacts/dryrun/<arch>__<shape>__<mesh>.json and are the
single source of truth for EXPERIMENTS.md §Dry-run and §Roofline.

Usage:
  python -m repro.launch.dryrun --arch deepseek-coder-33b --shape train_4k
  python -m repro.launch.dryrun --all [--mesh single|multi|both] [--skip-existing]
"""

import argparse
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, get_config, list_archs
from repro.configs.base import ModelConfig, ShapeCfg
from repro.launch import hlo_cost
from repro.launch.mesh import (axis_map_for, data_axes_of,
                               make_production_mesh, mesh_axis_sizes)
from repro.models import optflags
from repro.models.partition import batch_specs, cache_specs, param_specs
from repro.models.sharding import logical_axis_rules
from repro.models.transformer import Model, input_specs
from repro.optim import adamw

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                            "artifacts", "dryrun")

# long_500k is skipped for pure full-attention architectures (DESIGN.md §5).
def cell_applicable(cfg: ModelConfig, shape: ShapeCfg) -> tuple[bool, str]:
    if shape.name == "long_500k" and not cfg.sub_quadratic:
        return False, "pure full-attention arch: no sub-quadratic mechanism"
    return True, ""


def _named(tree, mesh):
    return jax.tree.map(lambda spec: NamedSharding(mesh, spec), tree,
                        is_leaf=lambda x: isinstance(x, P))


def build_cell(cfg: ModelConfig, shape: ShapeCfg, mesh):
    """Returns (fn, abstract_args, in_shardings) for one cell."""
    model = Model(cfg)
    axes = mesh_axis_sizes(mesh)
    data_axes = data_axes_of(mesh)
    params_abs = jax.eval_shape(lambda: model.init(jax.random.key(0)))
    p_specs = param_specs(params_abs, axes, data_axes,
                          kv_heads=cfg.n_kv_heads or None)
    inputs = input_specs(cfg, shape)
    b_specs = batch_specs(inputs, axes, data_axes)

    if shape.kind == "train":
        opt_cfg = adamw.AdamWConfig()
        opt_abs = jax.eval_shape(lambda: adamw.init(params_abs, opt_cfg))
        # ZeRO-style: optimizer moments shard exactly like their params
        o_specs = adamw.OptState(P(), p_specs, p_specs, None)

        def train_step(params, opt_state, batch):
            loss, grads = jax.value_and_grad(model.loss)(params, batch)
            params, opt_state = adamw.apply(params, grads, opt_state, opt_cfg)
            return loss, params, opt_state

        args = (params_abs, opt_abs, inputs)
        shardings = (_named(p_specs, mesh), _named(o_specs, mesh),
                     _named(b_specs, mesh))
        return train_step, args, shardings

    if shape.kind == "prefill":
        def prefill_step(params, batch):
            return model.hidden_states(params, batch["tokens"],
                                       batch.get("enc_frames"), remat=False)
        args = (params_abs, inputs)
        return prefill_step, args, (_named(p_specs, mesh),
                                    _named(b_specs, mesh))

    # decode
    if optflags.enabled("bf16params"):
        params_abs = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16)
            if jnp.issubdtype(s.dtype, jnp.floating) else s, params_abs)
    cache_abs = jax.eval_shape(
        lambda: model.init_cache(shape.global_batch, shape.seq_len))
    c_specs = cache_specs(cache_abs, axes, data_axes)

    def serve_step(params, cache, batch):
        return model.decode_step(params, cache, batch["tokens"], batch["pos"])

    args = (params_abs, cache_abs, inputs)
    shardings = (_named(p_specs, mesh), _named(c_specs, mesh),
                 _named(b_specs, mesh))
    return serve_step, args, shardings


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             artifact_dir: str = ARTIFACT_DIR,
             opts: tuple[str, ...] = ()) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh_name = "multi" if multi_pod else "single"
    record: dict = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                    "ok": False, "opts": list(opts)}
    ok, why = cell_applicable(cfg, shape)
    if not ok:
        record.update(skipped=True, reason=why, ok=True)
        return record
    if "sparseffn" in opts and shape.kind != "decode":
        record.update(skipped=True, ok=True,
                      reason="sparseffn applies to serve cells only")
        return record

    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.perf_counter()
    try:
        with optflags.optimizations(opts), mesh, \
                logical_axis_rules(axis_map_for(mesh)):
            fn, args, shardings = build_cell(cfg, shape, mesh)
            lowered = jax.jit(fn, in_shardings=shardings).lower(*args)
            t_lower = time.perf_counter() - t0
            t1 = time.perf_counter()
            compiled = lowered.compile()
            t_compile = time.perf_counter() - t1

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        if isinstance(cost, list):
            cost = cost[0] if cost else {}
        # trip-count-aware per-device terms (XLA's cost_analysis counts
        # while bodies once — useless for scan-over-layers models)
        deep = hlo_cost.analyze_compiled(compiled)
        record.update(
            ok=True,
            lower_s=round(t_lower, 2),
            compile_s=round(t_compile, 2),
            flops=deep["flops"],
            hlo_bytes=deep["bytes"],
            collectives=dict(deep["coll"],
                             count=deep["coll_count"]),
            coll_bytes=deep["coll_bytes"],
            xla_flops_raw=float(cost.get("flops", -1.0)),
            xla_bytes_raw=float(cost.get("bytes accessed", -1.0)),
            devices=int(mesh.devices.size),
            memory_analysis=_mem_to_dict(mem),
            params_count=cfg.params_count(),
            active_params=cfg.active_params_count(),
        )
    except Exception as e:  # noqa: BLE001 — record and continue the sweep
        record.update(error=f"{type(e).__name__}: {e}",
                      traceback=traceback.format_exc()[-4000:])
    return record


def _mem_to_dict(mem) -> dict:
    if mem is None:
        return {"available": False}
    out = {"available": True}
    for attr in ("temp_size_in_bytes", "argument_size_in_bytes",
                 "output_size_in_bytes", "alias_size_in_bytes",
                 "generated_code_size_in_bytes", "peak_memory_in_bytes"):
        if hasattr(mem, attr):
            out[attr] = int(getattr(mem, attr))
    if len(out) == 1:
        out["repr"] = str(mem)[:2000]
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--out", default=ARTIFACT_DIR)
    ap.add_argument("--opts", default="",
                    help="comma-separated optflags ("
                         f"{','.join(optflags.ALL_FLAGS)}); artifacts get an "
                         "__opt-<flags> suffix")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    archs = [args.arch] if args.arch else list_archs()
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    opts = tuple(o for o in args.opts.split(",") if o)
    suffix = f"__opt-{'-'.join(opts)}" if opts else ""

    n_fail = 0
    for arch in archs:
        for shape in shapes:
            for multi in meshes:
                mesh_name = "multi" if multi else "single"
                path = os.path.join(
                    args.out, f"{arch}__{shape}__{mesh_name}{suffix}.json")
                if args.skip_existing and os.path.exists(path):
                    with open(path) as f:
                        if json.load(f).get("ok"):
                            print(f"[skip] {arch} {shape} {mesh_name}")
                            continue
                print(f"[cell] {arch} {shape} {mesh_name} opts={opts} ...",
                      flush=True)
                rec = run_cell(arch, shape, multi, args.out, opts=opts)
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                if rec.get("skipped"):
                    print(f"  skipped: {rec['reason']}")
                elif rec["ok"]:
                    print(f"  ok: lower={rec['lower_s']}s "
                          f"compile={rec['compile_s']}s "
                          f"flops={rec['flops']:.3e} "
                          f"coll={rec['collectives']['count']}", flush=True)
                else:
                    n_fail += 1
                    print(f"  FAIL: {rec['error']}", flush=True)
    print(f"done; failures={n_fail}")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
