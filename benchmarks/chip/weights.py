"""Sparse weight structure from a seed, for any architecture.

The benchmark makes the weights, so that the reference can make them again
from the seed and take nothing from the program; the architecture's module
(``arch/<name>.py``) lays them out and draws them.  What is shared is the
structure: every projection weight is sparse at the configuration's
density, exactly ``floor(blocks · density)`` of its blocks kept, chosen at
random, at the block shape the configuration gives that role
(``sparsity.mask_blocks``, in ``(in, out)`` order).  The structure is data,
never the program's choice: a plan that serves these weights at other
blocks prunes some non-zeros away, and the harness's ``nnz_short`` check
sees it.

A role is ``{role: (in, out, fanout)}`` (``arch.roles``): ``fanout``
copies of an ``(in, out)`` weight in each layer, 1 for a plain projection.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def seed32(seed: int, stream: int) -> int:
    """A 32-bit key for ``stream`` of the run's ``seed`` (any size)."""
    return int(np.random.SeedSequence([seed, stream]).generate_state(1)[0])


def masks(cfg: dict, roles: dict) -> dict[str, tuple[int, int]]:
    """The block shape ``(bn, bk)`` of each role's mask, from the
    configuration; each has to tile its weight."""
    blocks = cfg["sparsity"]["mask_blocks"]
    out = {}
    for role, (n, k, _) in roles.items():
        bn, bk = blocks[role]
        if n % bn or k % bk:
            raise ValueError(f"{role}: mask block {bn}x{bk} does not tile "
                             f"its {n}x{k} weight")
        out[role] = (bn, bk)
    return out


def nnz_per_layer(roles: dict, masks: dict, density: float) -> dict[str, int]:
    """Non-zero weights of each role in one layer, over its fanout."""
    return {role: fan * _keep(n // masks[role][0], k // masks[role][1],
                              density) * masks[role][0] * masks[role][1]
            for role, (n, k, fan) in roles.items()}


def nnz_blocks(roles: dict, masks: dict, density: float) -> dict[str, int]:
    """Non-zero blocks per layer of each role, over its fanout."""
    return {role: fan * _keep(n // masks[role][0], k // masks[role][1],
                              density)
            for role, (n, k, fan) in roles.items()}


def _keep(gn: int, gk: int, density: float) -> int:
    return max(int(gn * gk * density), 1)


def mask(key, layers: int, n: int, k: int, block: tuple, density: float):
    """Keep-masks ``(layers, n, k)`` of one role: in each layer exactly
    ``_keep`` of its ``block``-shaped blocks, chosen at random by ``key``."""
    a, b = block
    gn, gk = n // a, k // b
    u = jax.random.uniform(key, (layers, gn * gk))
    rank = jnp.argsort(jnp.argsort(u, axis=1), axis=1)
    keep = (rank < _keep(gn, gk, density)).reshape(layers, gn, 1, gk, 1)
    return jnp.broadcast_to(keep, (layers, gn, a, gk, b)).reshape(
        layers, n, k)
