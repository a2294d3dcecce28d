"""Where the benchmark's data lives, found by name.

``BENCHMARK.json`` at the checkout's root names the cells; each cell names a
configuration (``configs/<name>.json``) and a traffic mix
(``traffic/<name>.json``); each configuration names its architecture
(``"arch"``), whose module ``arch/<name>.py`` holds everything that depends
on it; and each per-layer metric is read by ``metrics/<name>.py``.  Adding
a cell, mix, architecture or metric adds files; nothing here lists them.
"""

from __future__ import annotations

import functools
import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return _json(os.path.join(root, "BENCHMARK.json"))


def config(name: str, here: str = HERE) -> dict:
    """``configs/<name>.json``; it has to name its architecture."""
    path = os.path.join(here, "configs", f"{name}.json")
    cfg = _json(path)
    if "arch" not in cfg:
        raise ValueError(f"{path} names no architecture: give it "
                         f"\"arch\": \"<name>\" for a module arch/<name>.py")
    return cfg


def traffic(name: str, here: str = HERE) -> dict:
    return _json(os.path.join(here, "traffic", f"{name}.json"))


@functools.cache
def _module(kind: str, name: str, path: str):
    """The module at ``path``, loaded once per path."""
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, here: str = HERE):
    """``read(ctx) -> float | None`` of ``metrics/<name>.py``."""
    return _module("metric", name, os.path.join(here, "metrics",
                                                f"{name}.py")).read


def arch(name: str, here: str = HERE):
    """The module ``arch/<name>.py``: an architecture's dims, roles,
    weights, program configuration, reference and counts."""
    path = os.path.join(here, "arch", f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no architecture {name!r}: {path} does not "
                                f"exist")
    return _module("arch", name, path)


def listing(here: str = HERE) -> dict[str, list[str]]:
    """Every configuration, traffic mix, architecture and metric reader on
    disk."""
    def names(sub: str, ext: str) -> list[str]:
        d = os.path.join(here, sub)
        return sorted(f[: -len(ext)] for f in os.listdir(d)
                      if f.endswith(ext) and not f.startswith("_"))
    return {"configs": names("configs", ".json"),
            "traffic": names("traffic", ".json"),
            "arch": names("arch", ".py"),
            "metrics": names("metrics", ".py")}


def dims(cfg: dict, here: str = HERE) -> dict:
    """The configuration's dims, as its architecture reads them."""
    return arch(cfg["arch"], here).dims(cfg)


def cell(bench: dict, workload: str, here: str = HERE) -> dict:
    """One cell: its entry, configuration, architecture, mix, and the
    metrics it reports."""
    ws = {w["name"]: w for w in bench["workloads"]}
    if workload not in ws:
        raise KeyError(f"no workload {workload!r}; have {sorted(ws)}")
    w = ws[workload]

    def mine(ms):
        return [m for m in ms if workload in m.get("workloads", [workload])]
    cfg = config(w["config"], here)
    return {"workload": w, "config": cfg, "arch": arch(cfg["arch"], here),
            "mix": traffic(w["traffic"], here),
            "end_to_end": mine(bench["end_to_end"]),
            "per_layer": mine(bench["per_layer"])}
