"""Small shared helpers for shard_map-based collectives code.

jax tracks varying-manual-axes (vma) in avals inside shard_map: fresh
literals (zeros/full) are "unvarying" and cannot meet device-varying values
in a scan carry without an explicit cast. `full_varying` builds a filled
array that carries the vma of a reference value.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

shard_map = jax.shard_map


def vma_of(x) -> tuple:
    return tuple(jax.typeof(x).vma)


def full_varying(shape, fill, dtype, vma: tuple):
    x = jnp.full(shape, fill, dtype)
    if not vma:
        return x
    return jax.lax.pcast(x, vma, to="varying")
