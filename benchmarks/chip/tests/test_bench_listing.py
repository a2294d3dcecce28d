"""Configurations, mixes, architectures and metrics are found by name, as
files."""

import json
import os
import shutil

import spec


def test_new_files_are_found_without_editing_any(tmp_path):
    here = tmp_path / "chip"
    shutil.copytree(spec.HERE, here,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {p: open(p, "rb").read()
              for p in (str(f) for f in here.rglob("*") if f.is_file())}
    with open(here / "configs" / "chatglm3-6b.json") as f:
        cfg = json.load(f)
    cfg["num_layers"] = 1
    (here / "configs" / "dummy-model.json").write_text(json.dumps(cfg))
    (here / "traffic" / "dummy-mix.json").write_text(
        (here / "traffic" / "chat.json").read_text())
    (here / "metrics" / "dummy.metric_ms.py").write_text(
        "def read(ctx):\n    return ctx['x'] * 2\n")
    bench = {"workloads": [{"name": "dummy-model.dummy-mix",
                            "config": "dummy-model", "traffic": "dummy-mix",
                            "chips": 1}],
             "end_to_end": [{"name": "itl_p95_ms"}],
             "per_layer": [{"name": "dummy.metric_ms",
                            "workloads": ["dummy-model.dummy-mix"]},
                           {"name": "elsewhere", "workloads": ["other"]}]}

    listed = spec.listing(str(here))
    assert "dummy-model" in listed["configs"]
    assert "dummy-mix" in listed["traffic"]
    assert "dummy.metric_ms" in listed["metrics"]
    c = spec.cell(bench, "dummy-model.dummy-mix", str(here))
    assert spec.dims(c["config"], str(here))["n_layers"] == 1
    assert c["arch"].__file__ == str(here / "arch" / "gqa.py")
    assert c["mix"]["loop"] == "open"
    assert [m["name"] for m in c["per_layer"]] == ["dummy.metric_ms"]
    assert spec.metric_reader("dummy.metric_ms", str(here))({"x": 4}) == 8
    for p, data in before.items():
        assert open(p, "rb").read() == data, p


def test_every_named_file_exists():
    bench = spec.benchmark()
    listed = spec.listing()
    for w in bench["workloads"]:
        assert w["config"] in listed["configs"]
        assert w["traffic"] in listed["traffic"]
        spec.cell(bench, w["name"])
    assert {m["name"] for m in bench["per_layer"]} <= set(listed["metrics"])
    for c in bench["configs"]:
        cfg = spec.config(c["name"])
        assert cfg["arch"] in listed["arch"]
        assert c["file"] == os.path.relpath(
            os.path.join(spec.HERE, "configs", c["name"] + ".json"), spec.ROOT)
        assert cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert set(cfg["published"]) == set(c["reduced"])
