"""Beyond-baseline optimization flags (§Perf hillclimbing).

The paper-faithful baseline keeps all flags OFF; each hillclimb iteration
enables one and re-lowers, so EXPERIMENTS.md §Perf can attribute every
delta.  Flags:

  padheads  — pad attention head counts up to a multiple of the TP degree
              (56→64 heads on a 16-way axis): kills XLA's "involuntary full
              rematerialization" resharding all-gathers on the (B,S,H,D)
              reshapes, at the price of ~H_pad/H extra attention FLOPs.
  replkv    — replicate the (small) K/V projections when n_kv_heads doesn't
              divide the TP degree, instead of sharding their flat output
              dim (which forces replicate-and-repartition copies).
  saveremat — remat policy keeps each block's OUTPUT (post-all-reduce), so
              the backward recompute does not replay TP collectives.
  sparseffn — serve-time FFN weights stored in the SnipSnap-chosen
              block-bitmap format: payload-only weight streams (gather-BMM
              over non-zero blocks + segment-sum), cutting decode weight
              traffic by the block density.
  seqpar    — Megatron-style sequence parallelism: the residual stream is
              sharded along S on the model axis between blocks, so XLA
              lowers the TP output-projection psum as reduce-scatter and
              re-gathers at the next projection — ~2× fewer link-bytes than
              all-reduce (which is internally RS+AG).
"""

from __future__ import annotations

import contextlib
import threading

_state = threading.local()

ALL_FLAGS = ("padheads", "replkv", "saveremat", "sparseffn", "seqpar",
             "bf16params")
# bf16params — serve with bf16 parameters (cast once at load): decode is a
#              weight-stream problem; fp32 master copies belong to training.


def active() -> frozenset:
    return getattr(_state, "flags", frozenset())


def enabled(flag: str) -> bool:
    return flag in active()


@contextlib.contextmanager
def optimizations(flags):
    flags = frozenset(flags)
    unknown = flags - set(ALL_FLAGS)
    if unknown:
        raise ValueError(f"unknown optimization flags: {sorted(unknown)}")
    prev = active()
    _state.flags = flags
    try:
        yield
    finally:
        _state.flags = prev
