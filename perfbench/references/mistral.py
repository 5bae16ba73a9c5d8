"""Mistral-7B's decoder as published (mistralai/Mistral-7B-v0.1
config.json and the model card's description): pre-norm blocks, RMSNorm,
rotary embeddings on q and k (rotate-half convention, theta from the
config), grouped-query attention under a causal sliding-window mask,
SwiGLU, untied output head, no biases.

RMSNorm's epsilon is the configuration file's `rms_norm_eps`, which holds
the value that is run: 1e-6, fixed in the program, where the model
publishes 1e-5 (the file's `departures` say so).

Parameter paths are the ones the program's tree uses, so that one spec
serves both; the adapter checks the program's own shapes against it.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from perfbench.references.precision import einsum, matmul

INIT_STD = 0.02  # the family's initializer_range


def layer_spec(c: dict, i: int) -> dict:
    d, h, kv, dh, ff = (c["hidden_size"], c["num_attention_heads"],
                        c["num_key_value_heads"], c["head_dim"],
                        c["intermediate_size"])
    b = f"block{i}"
    return {
        f"{b}/norm1/scale": ((d,), None),
        f"{b}/attn/q/kernel": ((d, h * dh), INIT_STD),
        f"{b}/attn/k/kernel": ((d, kv * dh), INIT_STD),
        f"{b}/attn/v/kernel": ((d, kv * dh), INIT_STD),
        f"{b}/attn/out/kernel": ((h * dh, d), INIT_STD),
        f"{b}/norm2/scale": ((d,), None),
        f"{b}/mlp/gate/kernel": ((d, ff), INIT_STD),
        f"{b}/mlp/up/kernel": ((d, ff), INIT_STD),
        f"{b}/mlp/down/kernel": ((ff, d), INIT_STD),
    }


def outer_spec(c: dict) -> dict:
    d, v = c["hidden_size"], c["vocab_size"]
    return {"embed": ((v, d), INIT_STD), "norm_f/scale": ((d,), None),
            "lm_head/kernel": ((d, v), INIT_STD)}


def param_spec(c: dict) -> dict:
    spec = outer_spec(c)
    for i in range(c["num_hidden_layers"]):
        spec.update(layer_spec(c, i))
    return spec


def rms_norm(x, scale, eps: float):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rotary(x, theta: float):
    """x: (s, heads, dh), positions 0..s-1."""
    s, _, dh = x.shape
    half = dh // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(q, k, v, window: int | None, precision: str, block: int = 1024):
    """One sequence. q: (s, h, dh); k, v: (s, kv, dh). Plain softmax over
    the keys each query may see; done a block of queries at a time, and
    recomputed in the backward pass, so that s x s scores never sit in
    memory at once."""
    s, h, dh = q.shape
    kv = k.shape[1]
    g = h // kv
    block = next(b for b in range(min(block, s), 0, -1) if s % b == 0)
    qb = q.reshape(s // block, block, kv, g, dh)
    starts = jnp.arange(0, s, block)
    key_pos = jnp.arange(s)

    @jax.checkpoint
    def one(args):
        qi, start = args
        scores = einsum("qkgd,skd->kgqs", qi, k, precision) / math.sqrt(dh)
        q_pos = start + jnp.arange(block)
        keep = key_pos[None, :] <= q_pos[:, None]
        if window is not None:
            keep &= (q_pos[:, None] - key_pos[None, :]) < window
        probs = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), -1)
        return einsum("kgqs,skd->qkgd", probs, v, precision)

    return jax.lax.map(one, (qb, starts)).reshape(s, h * dh)


def layer(x, w: dict, c: dict, precision: str):
    """x: (s, d) of one sequence; w: this block's weights, nested."""
    h, kv, dh = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    s = x.shape[0]
    y = rms_norm(x, w["norm1"]["scale"], c["rms_norm_eps"])
    q = matmul(y, w["attn"]["q"]["kernel"], precision).reshape(s, h, dh)
    k = matmul(y, w["attn"]["k"]["kernel"], precision).reshape(s, kv, dh)
    v = matmul(y, w["attn"]["v"]["kernel"], precision).reshape(s, kv, dh)
    q, k = rotary(q, c["rope_theta"]), rotary(k, c["rope_theta"])
    o = attention(q, k, v, c.get("sliding_window"), precision)
    x = x + matmul(o, w["attn"]["out"]["kernel"], precision)
    y = rms_norm(x, w["norm2"]["scale"], c["rms_norm_eps"])
    gate = matmul(y, w["mlp"]["gate"]["kernel"], precision)
    up = matmul(y, w["mlp"]["up"]["kernel"], precision)
    return x + matmul(jax.nn.silu(gate) * up, w["mlp"]["down"]["kernel"], precision)


def head(x, params: dict, c: dict, precision: str):
    return matmul(rms_norm(x, params["norm_f"]["scale"], c["rms_norm_eps"]),
                  params["lm_head"]["kernel"], precision)


def logits_one(params: dict, tokens, c: dict, precision: str = "f32"):
    """tokens: (s,) of one sequence -> (s, vocab) float32 logits."""
    x = params["embed"].astype(jnp.float32)[tokens]
    for i in range(c["num_hidden_layers"]):
        x = jax.checkpoint(lambda x, w: layer(x, w, c, precision))(
            x, params[f"block{i}"])
    return head(x, params, c, precision)


def loss_rows(params: dict, batch, c: dict, precision: str = "f32"):
    """Summed next-token cross-entropy over the rows of `batch` =
    (tokens (n, s), labels (n, s)); the caller divides by the batch's
    tokens. One row at a time."""
    tokens, labels = batch

    @jax.checkpoint
    def row(tl):
        t, l = tl
        lg = logits_one(params, t, c, precision)
        lse = jax.nn.logsumexp(lg, -1)
        return jnp.sum(lse - jnp.take_along_axis(lg, l[:, None], -1)[:, 0])

    return jnp.sum(jax.lax.map(row, (tokens, labels)))


def units(batch) -> int:
    """What the loss is a mean over: tokens."""
    return batch[0].shape[0] * batch[0].shape[1]
