"""The one generator. A traffic mix is a data file under traffic/ and this
module turns its parameters and the seed into inputs; nothing here knows a
cell by name. Every seed asks for the same work: the shapes are the mix's,
only the token and pixel values are drawn from the seed.
"""

from __future__ import annotations


# -- training: a pool of batches, made on the device --------------------------

def train_batches(traffic: dict, cfg: dict, seed: int, rank: int = 0,
                  count: int | None = None):
    """`count` (default: the mix's pool) batches for `rank`, each a tuple
    (inputs, labels) on the default device, from one jitted call. Batch k of
    the run is pool[k % len(pool)]; all rows differ."""
    import jax
    import jax.numpy as jnp

    from perfbench.weights import seed_key

    n = count or traffic["pool"]
    b = traffic["batch"]
    key = jax.random.fold_in(seed_key(seed), 1_000_003 + rank)

    if traffic["kind"] == "tokens":
        s, vocab = traffic["seq"], cfg["vocab_size"]

        def make(key):
            toks = jax.random.randint(key, (n, b, s), 0, vocab, jnp.int32)
            return toks, jnp.roll(toks, -1, axis=2)
    elif traffic["kind"] == "images":
        size, ch, classes = cfg["image_size"], cfg["in_channels"], cfg["num_classes"]

        def make(key):
            k1, k2 = jax.random.split(key)
            return (jax.random.normal(k1, (n, b, size, size, ch), jnp.float32),
                    jax.random.randint(k2, (n, b), 0, classes, jnp.int32))
    else:
        raise ValueError(f"unknown training traffic kind {traffic['kind']!r}")

    xs, ys = jax.jit(make)(key)
    return [(xs[i], ys[i]) for i in range(n)]


def sample_input(traffic: dict, cfg: dict):
    """Shape and type of one row of a training batch's inputs."""
    import jax
    import jax.numpy as jnp

    if traffic["kind"] == "tokens":
        return jax.ShapeDtypeStruct((1, traffic["seq"]), jnp.int32)
    size = cfg["image_size"]
    return jax.ShapeDtypeStruct((1, size, size, cfg["in_channels"]), jnp.float32)


def units_per_step(traffic: dict) -> int:
    """Tokens or images one rank takes a step."""
    return traffic["batch"] * traffic.get("seq", 1)
