"""Where the benchmark's data lives, found by name.

``BENCHMARK.json`` at the checkout's root names the cells; each cell names a
configuration (``configs/<name>.json``) and a traffic mix
(``traffic/<name>.json``), and each per-layer metric is read by
``metrics/<name>.py``.  Adding a cell, mix or metric adds files; nothing
here lists them.
"""

from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

#: The configuration's dims, as the harness and the reference use them.
DIMS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff",
        "vocab", "rope_fraction", "rope_base", "norm_eps")


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return _json(os.path.join(root, "BENCHMARK.json"))


def config(name: str, here: str = HERE) -> dict:
    return _json(os.path.join(here, "configs", f"{name}.json"))


def traffic(name: str, here: str = HERE) -> dict:
    return _json(os.path.join(here, "traffic", f"{name}.json"))


def metric_reader(name: str, here: str = HERE):
    """``read(ctx) -> float | None`` of ``metrics/<name>.py``."""
    path = os.path.join(here, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def listing(here: str = HERE) -> dict[str, list[str]]:
    """Every configuration, traffic mix and metric reader on disk."""
    def names(sub: str, ext: str) -> list[str]:
        d = os.path.join(here, sub)
        return sorted(f[: -len(ext)] for f in os.listdir(d)
                      if f.endswith(ext) and not f.startswith("_"))
    return {"configs": names("configs", ".json"),
            "traffic": names("traffic", ".json"),
            "metrics": names("metrics", ".py")}


def dims(cfg: dict) -> dict:
    """The configuration's dims: each entry of ``cfg["dims"]`` names a key
    of the published config or gives the number itself."""
    out = {}
    for k in DIMS:
        v = cfg["dims"][k]
        out[k] = cfg[v] if isinstance(v, str) else v
    return out


def cell(bench: dict, workload: str, here: str = HERE) -> dict:
    """One cell: its entry, configuration, mix, and the metrics it reports."""
    ws = {w["name"]: w for w in bench["workloads"]}
    if workload not in ws:
        raise KeyError(f"no workload {workload!r}; have {sorted(ws)}")
    w = ws[workload]

    def mine(ms):
        return [m for m in ms if workload in m.get("workloads", [workload])]
    return {"workload": w, "config": config(w["config"], here),
            "mix": traffic(w["traffic"], here),
            "end_to_end": mine(bench["end_to_end"]),
            "per_layer": mine(bench["per_layer"])}
