"""Weights from the seed, made by the benchmark and not by the program.

A configuration's plain reference states its parameters as a flat spec
{path: (shape, std)}: std > 0 draws normal(0, std), std == 0 zeros,
std is None ones (norm scales). Each leaf's key is the seed's key folded
with a hash of the leaf's path, so one leaf can be made alone and equals
the same leaf of the whole tree. The program is handed the tree; the
reference makes its own from the same seed by this same function.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A key for any whole-number seed, also past 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def _leaf(key, path: str, shape, std, dtype):
    if std is None:
        return jnp.ones(shape, dtype)
    if std == 0:
        return jnp.zeros(shape, dtype)
    k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)


def nest(flat: dict) -> dict:
    """{"a/b/c": x} -> {"a": {"b": {"c": x}}}"""
    out: dict = {}
    for path, leaf in flat.items():
        node = out
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = leaf
    return out


def flatten(tree: dict, prefix: str = "") -> dict:
    out = {}
    for k, v in tree.items():
        path = f"{prefix}/{k}" if prefix else k
        if isinstance(v, dict):
            out.update(flatten(v, path))
        else:
            out[path] = v
    return out


def generate(spec: dict, seed: int, dtype) -> dict:
    """The nested tree for `spec`, made on the default device in ONE jitted
    call, in the type it is used in."""
    dtype = jnp.dtype(dtype)

    def make(key):
        return nest({p: _leaf(key, p, shape, std, dtype)
                     for p, (shape, std) in spec.items()})

    return jax.jit(make)(seed_key(seed))
