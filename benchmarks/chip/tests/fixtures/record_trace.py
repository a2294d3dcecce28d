#!/usr/bin/env python3
"""Record a small device trace of the compressed serving path, for reading
by hand and for cutting the trace-reduction fixture from.

Serves a 2-layer, 512-wide dense GQA model through plan → prune → compress
→ mixer on the chip, with the harness's ``bench.admit`` / ``bench.step``
annotations around each admission and decode step, traces a few of each,
and writes under the directory it is given:

  * the profiler's ``.xplane.pb``;
  * ``summary.txt``: every plane and line, its event count, and the first
    events of each line with their stats.

  python benchmarks/chip/tests/fixtures/record_trace.py OUT_DIR
"""

from __future__ import annotations

import dataclasses
import glob
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))))
sys.path.insert(0, os.path.join(ROOT, "src"))


def main(out: str) -> int:
    import jax
    import numpy as np
    from repro.configs import get_config
    from repro.launch.mixer import Mixer, Request
    from repro.launch.serve import compressed_model
    from repro.models.transformer import Model

    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 1
    cfg = dataclasses.replace(get_config("chatglm3-6b"), n_layers=2,
                              d_model=512, n_heads=8, n_kv_heads=2,
                              d_ff=1024, vocab=4096)
    params = Model(cfg).init(jax.random.key(0))
    cm, pruned = compressed_model(cfg, params)
    mx = Mixer(cm, pruned, slots=4, max_len=512)
    rng = np.random.default_rng(0)

    def req(i, plen):
        return Request(uid=f"r{i}", prompt=rng.integers(0, cfg.vocab, plen),
                       max_new=8)

    for i in range(4):                       # warm every shape
        mx.admit(req(100 + i, 128))
    for _ in range(10):
        mx._step()
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)
    jax.profiler.start_trace(out)
    for i in range(2):
        with jax.profiler.TraceAnnotation("bench.admit"):
            if mx.active.all():
                mx._step()
            mx.admit(req(i, 128))
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench.step"):
            mx._step()
    jax.profiler.stop_trace()

    path = glob.glob(os.path.join(out, "**", "*.xplane.pb"), recursive=True)[0]
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    with open(os.path.join(out, "summary.txt"), "w") as f:
        for plane in pd.planes:
            f.write(f"PLANE {plane.name!r} stats={dict(plane.stats)}\n")
            for line in plane.lines:
                evs = list(line.events)
                f.write(f"  LINE {line.name!r} events={len(evs)}\n")
                for ev in evs[:40]:
                    f.write(f"    {ev.name!r} start={ev.start_ns} "
                            f"dur={ev.duration_ns} "
                            f"stats={dict(ev.stats)}\n"[:1500])
    print(f"wrote {path} ({os.path.getsize(path)} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
