"""EvaByte's decoder as published (EvaByte/EvaByte config.json) with EVA
attention in its chunked deterministic form (arXiv:2302.04542), straight
from the equations: float32 residual stream, RMSNorm with the scale stored
as an offset from 1, rotary embeddings on q and k (rotate-half, theta from
the config), SwiGLU, no biases, an untied head that gives the next
`num_pred_heads` bytes' logits at every position.

Attention of one head, window W = `window_size`, chunk C = `chunk_size`:
    a_m    = phi . k_m,  pi = softmax of a over the C positions of chunk c
    khat_c = sum_m pi_m k_m + mu,   vhat_c = sum_m pi_m v_m
    query t sees the keys m <= t of its own window, and the summaries of
    every chunk of an earlier window (c < (W / C) * floor(t / W)), under one
    softmax of q_t . key / sqrt(head_dim).
What config.json does not pin is listed under `assumed` in the
configuration's file.

Independent of tpunet.ops: dense masks, a block of query rows at a time so
that the (rows, s + s/C) scores fit. Parameter paths are the program's.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from perfbench.references.mistral import rotary
from perfbench.references.precision import einsum, matmul

ROWS = 64     # query rows a block of attention scores
MLP_ROWS = 4096


def _dims(c: dict):
    h = c["num_attention_heads"]
    return c["hidden_size"], h, c["hidden_size"] // h, c["intermediate_size"]


def layer_spec(c: dict, i: int) -> dict:
    d, h, dh, ff = _dims(c)
    std, vec = c["init_std"], 1.0 / math.sqrt(dh)
    b = f"block{i}"
    return {
        f"{b}/norm1/scale": ((d,), 0),
        f"{b}/attn/q/kernel": ((d, d), std),
        f"{b}/attn/k/kernel": ((d, d), std),
        f"{b}/attn/v/kernel": ((d, d), std),
        f"{b}/attn/out/kernel": ((d, d), std),
        f"{b}/attn/adaptive_phi": ((h, dh), vec),
        f"{b}/attn/adaptive_mu_k": ((h, dh), vec),
        f"{b}/norm2/scale": ((d,), 0),
        f"{b}/mlp/gate/kernel": ((d, ff), std),
        f"{b}/mlp/up/kernel": ((d, ff), std),
        f"{b}/mlp/down/kernel": ((ff, d), std),
    }


def param_spec(c: dict) -> dict:
    d, v, std = c["hidden_size"], c["vocab_size"], c["init_std"]
    spec = {"embed": ((v, d), std), "norm_f/scale": ((d,), 0),
            "lm_head/kernel": ((d, v * c["num_pred_heads"]), std)}
    for i in range(c["num_hidden_layers"]):
        spec.update(layer_spec(c, i))
    return spec


def rms_norm(x, offset, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + offset)


def summaries(k, v, phi, mu, chunk: int, precision: str):
    """k, v: (s, h, dh), k after the rotary -> khat, vhat: (s / chunk, h, dh)."""
    s, h, dh = k.shape
    kc, vc = k.reshape(s // chunk, chunk, h, dh), v.reshape(s // chunk, chunk, h, dh)
    pi = jax.nn.softmax(einsum("nchd,hd->nch", kc, phi, precision), axis=1)
    return (einsum("nch,nchd->nhd", pi, kc, precision) + mu,
            einsum("nch,nchd->nhd", pi, vc, precision))


def attention(q, k, v, khat, vhat, window: int, chunk: int, precision: str):
    """One sequence; q, k, v: (s, h, dh); khat, vhat: (s / chunk, h, dh)."""
    s, h, dh = q.shape
    rows = next(b for b in range(min(ROWS, s), 0, -1) if s % b == 0)
    keys, vals = jnp.concatenate([k, khat]), jnp.concatenate([v, vhat])
    m = jnp.arange(s)[None, :]
    cc = jnp.arange(s // chunk)[None, :]

    @jax.checkpoint
    def one(args):
        qi, start = args
        t = (start + jnp.arange(rows))[:, None]
        keep = jnp.concatenate(
            [(m <= t) & (m // window == t // window),
             cc < (window // chunk) * (t // window)], axis=1)
        scores = einsum("qhd,khd->hqk", qi, keys, precision) / math.sqrt(dh)
        probs = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), -1)
        return einsum("hqk,khd->qhd", probs, vals, precision)

    out = jax.lax.map(one, (q.reshape(s // rows, rows, h, dh), jnp.arange(0, s, rows)))
    return out.reshape(s, h * dh)


def mlp(y, w: dict, precision: str):
    """A block of rows at a time, in a loop the compiler sees unrolled: out
    of a `lax.map` it hoists the weights' preparation for every layer at
    once, 1.6 GB a layer that the chip does not have."""
    rows = next(b for b in range(min(MLP_ROWS, y.shape[0]), 0, -1) if y.shape[0] % b == 0)

    @jax.checkpoint
    def one(yb):
        gate = matmul(yb, w["gate"]["kernel"], precision)
        up = matmul(yb, w["up"]["kernel"], precision)
        return matmul(jax.nn.silu(gate) * up, w["down"]["kernel"], precision)

    return jnp.concatenate([one(y[i:i + rows]) for i in range(0, y.shape[0], rows)])


def layer(x, w: dict, c: dict, precision: str):
    """x: (s, d) float32 of one sequence; w: this block's weights, nested."""
    _, h, dh, _ = _dims(c)
    s = x.shape[0]
    a = w["attn"]
    y = rms_norm(x, w["norm1"]["scale"], c["rms_norm_eps"])
    q, k, v = (matmul(y, a[n]["kernel"], precision).reshape(s, h, dh) for n in "qkv")
    q, k = rotary(q, c["rope_theta"]), rotary(k, c["rope_theta"])
    khat, vhat = summaries(k, v, a["adaptive_phi"], a["adaptive_mu_k"],
                           c["chunk_size"], precision)
    o = attention(q, k, v, khat, vhat, c["window_size"], c["chunk_size"], precision)
    x = x + matmul(o, a["out"]["kernel"], precision)
    return x + mlp(rms_norm(x, w["norm2"]["scale"], c["rms_norm_eps"]), w["mlp"], precision)


def logits_one(params: dict, tokens, c: dict, precision: str = "f32"):
    """tokens: (s,) of one sequence -> (s, heads, vocab) float32 logits."""
    x = params["embed"].astype(jnp.float32)[tokens]
    for i in range(c["num_hidden_layers"]):
        x = jax.checkpoint(lambda x, w: layer(x, w, c, precision))(
            x, params[f"block{i}"])
    y = rms_norm(x, params["norm_f"]["scale"], c["rms_norm_eps"])
    lg = matmul(y, params["lm_head"]["kernel"], precision)
    return lg.reshape(x.shape[0], c["num_pred_heads"], c["vocab_size"])


def loss_rows(params: dict, batch, c: dict, precision: str = "f32"):
    """Sum over the rows of `batch` = (tokens (n, s), labels (n, s), the next
    byte) of the row's mean cross-entropy over the (position, head) pairs
    whose target lies inside the row: head j at position t is asked for
    labels[t + j]. Every row has as many pairs, so the mean over rows of
    this is the mean over all pairs."""
    tokens, labels = batch
    heads = c["num_pred_heads"]

    @jax.checkpoint
    def row(tl):
        t, l = tl
        logp = jax.nn.log_softmax(logits_one(params, t, c, precision), -1)
        s = t.shape[0]
        total = 0.0
        for j in range(heads):
            total = total - jnp.sum(jnp.take_along_axis(
                logp[: s - j, j], l[j:, None], -1))
        return total / sum(s - j for j in range(heads))

    return jnp.sum(jax.lax.map(row, (tokens, labels)))


def units(batch) -> int:
    """What the sum of loss_rows is divided by: rows."""
    return batch[0].shape[0]
