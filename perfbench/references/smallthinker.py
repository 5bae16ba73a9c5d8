"""SmallThinker-21BA3B's decoder as published (PowerInfer/SmallThinker-
21BA3B-Instruct config.json; the family's description is arXiv:2507.20984):
pre-norm blocks, RMSNorm, grouped-query attention with a head size of its
own (28 x 128 is not the model's width), layers of two kinds by
`sliding_window_layout` and `rope_layout` (0: full causal attention with no
positional encoding at all; 1: causal sliding-window attention with rotary
embeddings, rotate-half, theta from the config), every layer a mixture of
`moe_num_primary_experts` gated ReLU experts of which a token takes
`moe_num_active_primary_experts`, a router that reads the normalised input
of the ATTENTION, untied head, no biases. Layer i of the model:

    h = norm1(x);  r = h W_r                       # (tokens, experts)
    a = attention(h W_q, h W_k, h W_v)             # by the layer's kind
    x' = x + a W_o;  u = norm2(x')
    S = top-k of r;  g = softmax(r[S])             # over the k chosen logits
    y = x' + sum over e in S HELD HERE of g_e (relu(u Wg_e) * (u Wu_e)) Wd_e

Departures from the published description, each also in the configuration's
file: (1) this chip's share. The configuration holds experts `first` ..
`first + held - 1` (`moe_experts_first`, `moe_num_primary_experts_held`) of
the `moe_num_primary_experts` the router spans, and a slice of the
vocabulary; what the absent experts would add is left out, here as in the
program, and the partial y goes on to the next layer. (2) softmax over the
chosen logits where the published code takes the softmax over all and
renormalises over the chosen: the same numbers. (3) what config.json does
not pin is listed under `assumed` in the configuration's file.

Independent of tpunet: no kernel, no sort, no row buffer. For each held
expert a weight a token (its gate where the token chose the expert, else
0) and a DENSE product over every token. Parameter paths are the program's,
so that one spec serves both.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from perfbench.references.mistral import attention, rms_norm, rotary
from perfbench.references.precision import matmul


def _held(c: dict) -> tuple[int, int]:
    return (c.get("moe_experts_first", 0),
            c.get("moe_num_primary_experts_held", c["moe_num_primary_experts"]))


def layer_spec(c: dict, i: int) -> dict:
    d, h, kv, dh = (c["hidden_size"], c["num_attention_heads"],
                    c["num_key_value_heads"], c["head_dim"])
    e, f, std = c["moe_num_primary_experts"], c["moe_ffn_hidden_size"], c["initializer_range"]
    held = _held(c)[1]
    b = f"block{i}"
    return {
        f"{b}/norm1/scale": ((d,), None),
        f"{b}/attn/q/kernel": ((d, h * dh), std),
        f"{b}/attn/k/kernel": ((d, kv * dh), std),
        f"{b}/attn/v/kernel": ((d, kv * dh), std),
        f"{b}/attn/out/kernel": ((h * dh, d), std),
        f"{b}/norm2/scale": ((d,), None),
        f"{b}/moe/router": ((d, e), std),
        f"{b}/moe/gate": ((held, d, f), std),
        f"{b}/moe/up": ((held, d, f), std),
        f"{b}/moe/down": ((held, f, d), std),
    }


def param_spec(c: dict) -> dict:
    d, v, std = c["hidden_size"], c["vocab_size"], c["initializer_range"]
    spec = {"embed": ((v, d), c.get("embed_initializer_range", std)),
            "norm_f/scale": ((d,), None),
            "lm_head/kernel": ((d, v), std)}
    for i in range(c["num_hidden_layers"]):
        spec.update(layer_spec(c, i))
    return spec


def route(h, router, c: dict, precision: str):
    """(chosen experts (s, k), their weights (s, k)) of one sequence."""
    r = matmul(h, router, precision)
    top, experts = jax.lax.top_k(r, c["moe_num_active_primary_experts"])
    return experts, jax.nn.softmax(top, axis=-1)


def experts_held(u, experts, gates, w: dict, c: dict, precision: str):
    """The held experts' part of the layer's output. u: (s, d). An expert
    at a time (a scan over the stacked matrices: sixteen unrolled bodies a
    layer cost the compiler minutes), every token through it, weighed by
    the token's gate for that expert (0 where the token did not choose
    it)."""
    first, held = _held(c)

    def one(out, args):
        e, wg, wu, wd = args
        weight = jnp.sum(jnp.where(experts == e, gates, 0.0), axis=-1)
        act = jax.nn.relu(matmul(u, wg, precision)) * matmul(u, wu, precision)
        return out + weight[:, None] * matmul(act, wd, precision), None

    ids = first + jnp.arange(held)
    return jax.lax.scan(one, jnp.zeros_like(u), (ids, w["gate"], w["up"], w["down"]))[0]


def layer(x, w: dict, c: dict, i: int, precision: str):
    """x: (s, d) of one sequence; w: this block's weights, nested."""
    h_, kv, dh = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    s = x.shape[0]
    a = w["attn"]
    h = rms_norm(x, w["norm1"]["scale"], c["rms_norm_eps"])
    experts, gates = route(h, w["moe"]["router"], c, precision)
    q = matmul(h, a["q"]["kernel"], precision).reshape(s, h_, dh)
    k = matmul(h, a["k"]["kernel"], precision).reshape(s, kv, dh)
    v = matmul(h, a["v"]["kernel"], precision).reshape(s, kv, dh)
    if c["rope_layout"][i]:
        q, k = rotary(q, c["rope_theta"]), rotary(k, c["rope_theta"])
    window = c["sliding_window_size"] if c["sliding_window_layout"][i] else None
    x = x + matmul(attention(q, k, v, window, precision), a["out"]["kernel"], precision)
    u = rms_norm(x, w["norm2"]["scale"], c["rms_norm_eps"])
    return x + experts_held(u, experts, gates, w["moe"], c, precision)


def logits_one(params: dict, tokens, c: dict, precision: str = "f32"):
    """tokens: (s,) of one sequence -> (s, vocab) float32 logits."""
    x = params["embed"].astype(jnp.float32)[tokens]
    for i in range(c["num_hidden_layers"]):
        x = jax.checkpoint(lambda x, w, i=i: layer(x, w, c, i, precision))(
            x, params[f"block{i}"])
    return matmul(rms_norm(x, params["norm_f"]["scale"], c["rms_norm_eps"]),
                  params["lm_head"]["kernel"], precision)


def loss_rows(params: dict, batch, c: dict, precision: str = "f32"):
    """Summed next-token cross-entropy over the rows of `batch` = (tokens
    (n, s), labels (n, s)), over the configuration's vocabulary (its slice);
    the caller divides by the batch's tokens. One row at a time."""
    tokens, labels = batch

    def row(tl):
        t, l = tl
        lg = logits_one(params, t, c, precision)
        lse = jax.nn.logsumexp(lg, -1)
        return jnp.sum(lse - jnp.take_along_axis(lg, l[:, None], -1)[:, 0])

    return jnp.sum(jax.lax.map(row, (tokens, labels)))


def units(batch) -> int:
    """What the loss is a mean over: tokens."""
    return batch[0].shape[0] * batch[0].shape[1]
