"""An architecture is found by name, as files: a configuration names its
module (``arch/<name>.py``), which serves the harness its weights,
reference and counts.  The dense GQA module gives what the harness gave
before it had architectures, bit for bit."""

import hashlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import counts
import run
import spec
import tracing
import weights

GQA = spec.arch("gqa")

#: A module over the gqa one that records each call the harness makes.
OTHER = '''"""The gqa architecture, recording each call."""
import os

import spec

_gqa = spec.arch("gqa", os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DIMS, CONTROL_DTYPES = _gqa.DIMS, _gqa.CONTROL_DTYPES
CALLS = []


def _recorded(name):
    def call(*args, **kw):
        CALLS.append(name)
        return getattr(_gqa, name)(*args, **kw)
    return call


for _name in ("dims", "program_config", "roles", "make", "program_tree",
              "logits", "decode_flops", "decode_kv_bytes",
              "step_weight_bytes"):
    globals()[_name] = _recorded(_name)
'''


def _files(d) -> dict:
    return {str(p): p.read_bytes() for p in d.rglob("*")
            if p.is_file() and "__pycache__" not in p.parts}


def _add_cell(tiny_bench, tmp_path, name: str, arch) -> tuple:
    """A configuration ``name``, the tiny one naming ``arch`` (none where
    None), and its decode cell, reporting the counting metrics; the cell's
    entry goes into a ``BENCHMARK.json`` of its own beside the program, so
    no file of the bench is edited."""
    cfg = json.loads((tiny_bench / "configs" / "tiny.json").read_text())
    del cfg["arch"]
    if arch is not None:
        cfg["arch"] = arch
    (tiny_bench / "configs" / f"{name}.json").write_text(json.dumps(cfg))
    bench = json.loads((tiny_bench / "BENCHMARK.json").read_text())
    cell = f"{name}.decode"
    bench["workloads"].append({"name": cell, "config": name,
                               "traffic": "decode", "chips": 1})
    for m in bench["per_layer"]:
        if m["name"] in ("decode.mfu", "decode_step_roofline"):
            m["workloads"].append(cell)
    root = tmp_path / "root"
    root.mkdir()
    (root / "src").symlink_to(os.path.join(spec.ROOT, "src"))
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return cell, root


def test_new_architecture_enters_by_files_alone(tiny_bench, tmp_path, capsys,
                                                monkeypatch):
    before = _files(tiny_bench)
    (tiny_bench / "arch" / "other.py").write_text(OTHER)
    cell, root = _add_cell(tiny_bench, tmp_path, "tiny-other", "other")
    # a CPU trace has no TPU plane and the CPU no peaks: both stood in for
    monkeypatch.setattr(tracing, "reduce", lambda path, chips=1: tracing.Trace(
        window_s=1.0, busy_s=0.5, steps=[], admits=[],
        breakdown={"device_ops": [], "idle_gaps": []}))
    monkeypatch.setitem(counts.PEAKS, "cpu", counts.PEAKS["TPU v5 lite"])
    argv = ["--workload", cell, "--seed", str(2**31 + 91), "--seconds", "2",
            "--trace", "1"]
    assert run.main(argv, root=str(root), here=str(tiny_bench),
                    require_chip=False, cache=False) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])

    assert out["correct"] is True
    assert out["metrics"]["decode.mfu"]["value"] > 0
    calls = set(spec.arch("other", str(tiny_bench)).CALLS)
    assert calls == {"dims", "program_config", "roles", "make",
                     "program_tree", "logits", "decode_flops",
                     "decode_kv_bytes", "step_weight_bytes"}
    after = _files(tiny_bench)
    assert {p: after[p] for p in before} == before


@pytest.mark.parametrize("arch,named", [(None, "configs/tiny-lost.json"),
                                        ("nowhere", "arch/nowhere.py")])
def test_missing_architecture_is_refused_by_name(tiny_bench, tmp_path, capsys,
                                                 arch, named):
    cell, root = _add_cell(tiny_bench, tmp_path, "tiny-lost", arch)
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", cell, "--seed", "1", "--seconds", "1"],
                 root=str(root), here=str(tiny_bench), require_chip=False,
                 cache=False)
    assert e.value.code != 0
    got = capsys.readouterr()
    assert got.out == ""
    assert str(tiny_bench / named) in got.err


# -- pins: the values the harness gave before architectures were modules --

#: sha256 of the weights (seed 7, 64x64 mask blocks, density 0.5; keys in
#: order, each key's name then its float32 bytes) and of the reference's
#: logits over 96 tokens, at test_bench_reference's small dims.  Logits
#: are taken on one CPU thread: XLA's CPU matmuls sum in another order
#: with more threads.
DIGESTS = {
    "chatglm3-6b": (
        "67890d58932ba3e74cd643659632b6595233f93fd1ad21ca33e3386098407d7a",
        "0ba16577074b19bf39f6be5023059c144a6b498a387e2eec1f763ce343f2e6f5"),
    "deepseek-coder-33b": (
        "264bdd1bc091fcbdb2c6f8caabfb5b2d262c4ae1ad08d1c25a98bdff0d5aa895",
        "19dacc800bbd2a0c9d5b127f23128617b23d92c097f94784de96dcc7ad25f1a5"),
}

#: Non-zero projection weights per layer and the FLOPs of decoding tokens
#: at contexts 1, 128, 1000 and 3071, at each configuration's dims.
FLOPS = {"chatglm3-6b": (101974016, 5669126144.0),
         "deepseek-coder-33b": (264372224, 6320488448.0)}

DIGEST_SCRIPT = '''
import hashlib, json, os, sys
os.sched_setaffinity(0, {int(sys.argv[2])})   # before XLA makes its threads
import numpy as np
sys.path.insert(0, sys.argv[1])
import spec
from test_bench_reference import SMALL
gqa = spec.arch("gqa")
out = {}
for name, dims in SMALL.items():
    w = gqa.make(7, dims, {r: (64, 64) for r in gqa.roles(dims)}, 0.5)
    h = hashlib.sha256()
    for k in sorted(w):
        h.update(k.encode())
        h.update(np.asarray(w[k]).tobytes())
    tokens = np.random.default_rng(0).integers(0, dims["vocab"], 96)
    ref = np.asarray(gqa.logits(w, tokens, np.arange(96), dims))
    out[name] = [h.hexdigest(), hashlib.sha256(ref.tobytes()).hexdigest()]
print(json.dumps(out))
'''


@pytest.fixture(scope="module")
def digests():
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cpu = min(os.sched_getaffinity(0))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
        [os.path.join(here, "tests"), os.path.join(spec.ROOT, "src")]))
    p = subprocess.run(
        [sys.executable, "-c", DIGEST_SCRIPT, here, str(cpu)], env=env,
        capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("name", sorted(DIGESTS))
def test_weights_and_reference_are_pinned(digests, name):
    assert tuple(digests[name]) == DIGESTS[name]


@pytest.mark.parametrize("name", sorted(FLOPS))
def test_decode_flops_are_pinned(name):
    cfg = spec.config(name)
    dims = spec.dims(cfg)
    roles = GQA.roles(dims)
    nnz = sum(weights.nnz_per_layer(roles, weights.masks(cfg, roles),
                                    cfg["sparsity"]["density"]).values())
    got = GQA.decode_token_flops(dims, nnz, np.array([1, 128, 1000, 3071]))
    assert (nnz, got) == FLOPS[name]
