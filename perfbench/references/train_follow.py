"""The training reference: follow the first steps of a job from the seed.

Given a configuration's plain reference (loss_rows, units), the weights and
batches the seed gives, and the optimizer the configuration states, take
three optimizer steps in float32 and report what the comparison reads:
each step's loss on rank 0, the per-leaf norm of the first gradient as the
optimizer gets it (after the mean over the ranks), that gradient itself for
the leaves the cell's file asks for, and the per-leaf norm of the
parameters' change after the three steps. The optimizer's own arithmetic,
one leaf at a time, is perfbench/optimizers/<name>.py.

Everything is done so that it fits beside nothing else on one chip: rows a
block at a time, the optimizer a leaf at a time, and (offload=True) the
optimizer's moments kept on the host between steps.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import optimizers, weights

STEPS = 3


# -- gradients, a block of rows at a time --------------------------------------

def _blocks(batch, rows: int):
    n = batch[0].shape[0]
    for i in range(0, n, rows):
        yield tuple(x[i:i + rows] for x in batch)


_add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=0)
_scale = jax.jit(lambda g, n: jax.tree.map(lambda x: x / n, g), donate_argnums=0)


def grad_fn(ref, cfg: dict, precision: str):
    """Summed loss over a block of rows and its gradient; built once a
    follow() so that the three steps share one trace."""
    return jax.jit(jax.value_and_grad(
        lambda p, b: ref.loss_rows(p, b, cfg, precision)))


def loss_and_grads(fn, ref, params: dict, batch, rows: int):
    """Mean loss over the batch and its gradient, as nested trees."""
    total, grads = 0.0, None
    for block in _blocks(batch, rows):
        loss, g = fn(params, block)
        total += float(loss)
        grads = g if grads is None else _add(grads, g)
        del g
    n = ref.units(batch)
    return total / n, _scale(grads, float(n))


_norm = jax.jit(lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))))
_mean_of = jax.jit(lambda g, other, world: (g + other) / world, donate_argnums=0)


def follow(ref, cfg: dict, spec: dict, seed: int, rank_batches: list,
           optimizer: dict, rows: int, precision: str = "f32",
           peer_grads=None, offload: bool = False,
           fault: str | None = None, keep_grads=()) -> dict:
    """rank_batches[r] = the STEPS batches of chip-holding rank r (rank 0's
    loss is the one reported). peer_grads(path, shape) gives the summed
    gradient of the ranks that have no chip (the twins), or is None. The
    world size is the chip ranks plus, if peer_grads, one. keep_grads names
    the leaves whose first gradient is handed back whole, on the host.

    fault plants a fault of the timed path in the reference, for the tests
    and the chip readings that set the limits' upper ends:
    "half_batch" leaves out the second half of every batch and takes the
    mean over the rest; "no_exchange" leaves out the mean over the ranks.
    """
    world = len(rank_batches) + (1 if peer_grads is not None else 0)
    params = weights.generate(spec, seed, jnp.float32)
    flat_p = weights.flatten(params)
    slots = {p: None for p in flat_p}
    losses, grad_norm, grad_leaf = [], {}, {}
    opt_mod = optimizers.find(optimizer)
    fn = grad_fn(ref, cfg, precision)
    for k in range(STEPS):
        grads = None
        for r, batches in enumerate(rank_batches):
            batch = batches[k]
            if fault == "half_batch":
                batch = tuple(x[: x.shape[0] // 2] for x in batch)
            loss, g = loss_and_grads(fn, ref, weights.nest(flat_p), batch, rows)
            if r == 0:
                losses.append(loss)
            if fault == "no_exchange" and r > 0:
                continue
            grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
        flat_g = weights.flatten(grads)
        del grads, g
        for path in list(flat_p):
            g = flat_g.pop(path)
            if fault != "no_exchange":
                if peer_grads is not None:
                    g = _mean_of(g, peer_grads(path, g.shape), float(world))
                elif world > 1:
                    g = g / world
            if k == 0:
                grad_norm[path] = float(_norm(g))
                if path in keep_grads:
                    grad_leaf[path] = np.asarray(g)
            s = slots[path]
            if s is None:
                s = tuple(jnp.zeros_like(g) for _ in range(opt_mod.SLOTS))
            elif offload:
                s = tuple(jnp.asarray(x) for x in s)
            flat_p[path], s = opt_mod.reference_leaf(flat_p[path], s, g, k + 1, optimizer)
            slots[path] = tuple(np.asarray(x) for x in s) if offload else s
            del g, s
    delta_norm = {}
    key = weights.seed_key(seed)
    for path, (shape, std) in spec.items():
        p0 = weights._leaf(key, path, shape, std, jnp.float32)
        delta_norm[path] = float(_norm(flat_p[path] - p0))
    return {"loss": losses, "grad_norm": grad_norm, "delta_norm": delta_norm,
            "grad_leaf": grad_leaf}
