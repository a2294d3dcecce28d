"""Shared set-up of the benchmark's CPU tests: the harness on ``sys.path``,
and a tiny copy of the benchmark (the chatglm3-6b configuration cut to
d_model 256, both mixes cut to 4 slots, the architectures and metric
readers as they are) that a CPU run can hold."""

import json
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)


def tiny_dims(cfg: dict) -> dict:
    cfg = dict(cfg)
    cfg.update({"num_layers": 2, "hidden_size": 256, "ffn_hidden_size": 512,
                "kv_channels": 64, "num_attention_heads": 4,
                "multi_query_group_num": 2, "padded_vocab_size": 512})
    # the blocks the plan serves at these widths
    cfg["sparsity"] = dict(cfg["sparsity"], mask_blocks={
        "attn.wq": [256, 128], "attn.wk": [256, 128], "attn.wv": [256, 128],
        "attn.wo": [256, 128], "ffn.w_gate": [256, 256],
        "ffn.w_up": [256, 256], "ffn.w_down": [512, 128]})
    return cfg


@pytest.fixture
def tiny_bench(tmp_path):
    """A benchmark directory (``BENCHMARK.json``, configs, traffic, arch,
    metrics) whose cells serve a tiny model; returns its path."""
    for sub in ("configs", "traffic"):
        (tmp_path / sub).mkdir()
    with open(os.path.join(HERE, "configs", "chatglm3-6b.json")) as f:
        cfg = tiny_dims(json.load(f))
    (tmp_path / "configs" / "tiny.json").write_text(json.dumps(cfg))
    for name in ("decode", "chat"):
        with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
            mix = json.load(f)
        mix.update({"slots": 4, "max_len": 192, "pool": 256})
        mix["prompt"].update({"median": 32, "min": 16, "max": 64,
                              "round_to": 16})
        mix["output"].update({"median": 8, "min": 4, "max": 32})
        if mix["loop"] == "open":
            mix["rate_per_s"] = 2.0
        (tmp_path / "traffic" / f"{name}.json").write_text(json.dumps(mix))
    for sub in ("arch", "metrics"):
        shutil.copytree(os.path.join(HERE, sub), tmp_path / sub,
                        ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(os.path.dirname(os.path.dirname(HERE)),
                           "BENCHMARK.json")) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        w["config"] = "tiny"
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp_path
