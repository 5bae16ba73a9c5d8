"""Keye-VL-2.0-30B-A3B's language model as its config.json and the published
description of its attention give it (Kwai-Keye/Keye-VL-2.0-30B-A3B; the
mechanism: the DeepSeek-V3.2-Exp report's sparse attention): pre-norm blocks,
RMSNorm, grouped-query attention whose keys an INDEXER selects, every layer a
mixture of SwiGLU experts, untied head, no biases. Text only: the three
position streams of `mrope_section` are equal and the rotary is the ordinary
one over the whole head. Layer i of the model, for one sequence:

    h = norm1(x)
    q, k, v = h Wq, h Wk, h Wv;  q, k = rotary(rmsnorm_head(q)), rotary(rmsnorm_head(k))
    # the indexer, on h DETACHED: 16 heads of 64 over one key head
    qI = rotary(h WqI);  kI = rotary(LayerNorm(h WkI));  w = (h Ww) / sqrt(16 * 64)
    I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])                      s <= t
    S_t = the min(t + 1, topk) keys of largest I[t, .], ties to the lower index
    a[t, .] = softmax over S_t of q_t . k_s / sqrt(128), a head;  o_t = sum_S a v
    x' = x + o Wo
    L_I = mean_t KL(stop_gradient(mean over heads of a[t, .]) || softmax over S_t of I[t, .])
    u = norm2(x');  r = u Wr;  E = the 8 best of r;  g = softmax(r[E])
    y = x' + sum over e in E HELD HERE of g_e (silu(u Wg_e) * (u Wu_e)) Wd_e

and the loss of a step is the mean next-token cross-entropy plus
`index_loss_weight` times the mean of L_I over the layers. The selection
passes no gradient: the cross-entropy reaches no weight of the indexer, and
L_I none but the indexer's (h, a's mean and the selection are constants of
it).

Departures and assumptions are in the configuration's file: this chip's
share (experts `first` .. `first + held - 1` of the `num_experts` the router
spans, a slice of the vocabulary; what the absent experts would add is left
out, here as in the program), and every size or form the row does not pin.

Independent of tpunet: no kernel, no threshold search, no mask operand. The
selection is `jax.lax.top_k`'s k-th value a query, the ties at it resolved
by a running count; attention and L_I a block of queries at a time against
all keys, recomputed in the backward pass. Parameter paths are the
program's, so that one spec serves both.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from perfbench.references.mistral import rms_norm, rotary
from perfbench.references.precision import einsum, matmul

BLOCK = 512  # queries at a time: (heads, 512, s) float32 scores


def _held(c: dict) -> tuple[int, int]:
    return c.get("experts_first", 0), c.get("num_local_experts", c["num_experts"])


def layer_spec(c: dict, i: int) -> dict:
    d, h, kv, dh = (c["hidden_size"], c["num_attention_heads"],
                    c["num_key_value_heads"], c["head_dim"])
    sa = c["sa_config"]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    e, f, std = c["num_experts"], c["moe_intermediate_size"], c["initializer_range"]
    held = _held(c)[1]
    b = f"block{i}"
    return {
        f"{b}/norm1/scale": ((d,), None),
        f"{b}/attn/q/kernel": ((d, h * dh), std),
        f"{b}/attn/k/kernel": ((d, kv * dh), std),
        f"{b}/attn/v/kernel": ((d, kv * dh), std),
        f"{b}/attn/out/kernel": ((h * dh, d), std),
        f"{b}/attn/q_norm/scale": ((dh,), None),
        f"{b}/attn/k_norm/scale": ((dh,), None),
        f"{b}/attn/index_q/kernel": ((d, hi * di), std),
        f"{b}/attn/index_k/kernel": ((d, di), std),
        f"{b}/attn/index_k_norm/scale": ((di,), None),
        f"{b}/attn/index_k_norm/bias": ((di,), 0),
        f"{b}/attn/index_w/kernel": ((d, hi), std),
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


def layer_norm(x, scale, bias, eps: float = 1e-6):
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def selection(scores, q_pos, top_k: int):
    """scores: (n, s) of the queries at positions q_pos -> (n, s) bool, True
    where key s is one of the query's min(t + 1, top_k) best keys s <= t,
    ties to the lower index."""
    n, s = scores.shape
    causal = jnp.arange(s)[None, :] <= q_pos[:, None]
    masked = jnp.where(causal, scores, -jnp.inf)
    k = min(top_k, s)
    best = jax.lax.top_k(masked, k)[0]  # (n, k), largest first
    want = jnp.minimum(q_pos + 1, k)
    tau = jnp.take_along_axis(best, (want - 1)[:, None], axis=1)
    above = masked > tau
    tied = causal & (masked == tau)
    need = want[:, None] - jnp.sum(above, axis=1, keepdims=True)
    return above | (tied & (jnp.cumsum(tied, axis=1) <= need))


def selected_attention(q, k, v, qi, ki, w, c: dict, precision: str):
    """One sequence. q: (s, h, dh); k, v: (s, kv, dh); qi: (s, hi, di); ki:
    (s, di); w: (s, hi). -> (o (s, h * dh), the sum over the queries of
    their KL, the pairs selected). A block of queries at a time."""
    s, h, dh = q.shape
    kv = k.shape[1]
    block = next(b for b in range(min(BLOCK, s), 0, -1) if s % b == 0)
    cut = lambda x: x.reshape(s // block, block, *x.shape[1:])  # noqa: E731
    stop = jax.lax.stop_gradient

    @jax.checkpoint
    def one(args):
        q_b, qi_b, w_b, start = args
        dots = einsum("qjd,sd->jqs", qi_b, ki, precision)
        scores = jnp.sum(jax.nn.relu(dots) * w_b.T[:, :, None], axis=0)  # (block, s)
        keep = selection(stop(scores), start + jnp.arange(block), c["sa_config"]["topk"])
        logits = einsum("qkgd,skd->kgqs", q_b.reshape(block, kv, h // kv, dh), k,
                        precision) / math.sqrt(dh)
        probs = jax.nn.softmax(jnp.where(keep, logits, -jnp.inf), -1)
        o = einsum("kgqs,skd->qkgd", probs, v, precision).reshape(block, h * dh)
        p = stop(jnp.mean(probs, axis=(0, 1)))
        log_i = jax.nn.log_softmax(jnp.where(keep, scores, -jnp.inf), -1)
        on = keep & (p > 0)
        kl = jnp.sum(jnp.where(on, p * (jnp.log(jnp.where(on, p, 1.0))
                                        - jnp.where(on, log_i, 0.0)), 0.0))
        return o, kl, jnp.sum(keep)

    o, kl, pairs = jax.lax.map(one, (cut(q), cut(qi), cut(w), jnp.arange(0, s, block)))
    return o.reshape(s, h * dh), jnp.sum(kl), jnp.sum(pairs)


def route(u, router, c: dict, precision: str):
    """(chosen experts (s, k), their weights (s, k)) of one sequence."""
    top, experts = jax.lax.top_k(matmul(u, router, precision), c["num_experts_per_tok"])
    return experts, jax.nn.softmax(top, axis=-1)


def experts_held(u, experts, gates, w: dict, c: dict, precision: str):
    """The held experts' part of the layer's output. u: (s, d). An expert at
    a time (a scan over the stacked matrices), every token through it,
    weighed by the token's gate for that expert (0 where the token did not
    choose it)."""
    first, held = _held(c)

    def one(out, args):
        e, wg, wu, wd = args
        weight = jnp.sum(jnp.where(experts == e, gates, 0.0), axis=-1)
        act = jax.nn.silu(matmul(u, wg, precision)) * matmul(u, wu, precision)
        return out + weight[:, None] * matmul(act, wd, precision), None

    ids = first + jnp.arange(held)
    return jax.lax.scan(one, jnp.zeros_like(u), (ids, w["gate"], w["up"], w["down"]))[0]


def indexer(h, a: dict, c: dict, precision: str):
    """(qI (s, hi, di), kI (s, di), w (s, hi)) from the DETACHED h."""
    sa = c["sa_config"]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    s = h.shape[0]
    h = jax.lax.stop_gradient(h)
    qi = rotary(matmul(h, a["index_q"]["kernel"], precision).reshape(s, hi, di),
                c["rope_theta"])
    ki = layer_norm(matmul(h, a["index_k"]["kernel"], precision),
                    a["index_k_norm"]["scale"], a["index_k_norm"]["bias"])
    ki = rotary(ki[:, None, :], c["rope_theta"])[:, 0]
    return qi, ki, matmul(h, a["index_w"]["kernel"], precision) * (hi * di) ** -0.5


def layer(x, w: dict, c: dict, precision: str):
    """x: (s, d) of one sequence; w: this block's weights, nested. ->
    (x after the block, the sum over the queries of their KL, the pairs)."""
    h_, kv, dh = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    s = x.shape[0]
    a, eps = w["attn"], c["rms_norm_eps"]
    h = rms_norm(x, w["norm1"]["scale"], eps)
    q = matmul(h, a["q"]["kernel"], precision).reshape(s, h_, dh)
    k = matmul(h, a["k"]["kernel"], precision).reshape(s, kv, dh)
    v = matmul(h, a["v"]["kernel"], precision).reshape(s, kv, dh)
    q = rotary(rms_norm(q, a["q_norm"]["scale"], eps), c["rope_theta"])
    k = rotary(rms_norm(k, a["k_norm"]["scale"], eps), c["rope_theta"])
    o, kl, pairs = selected_attention(q, k, v, *indexer(h, a, c, precision), c, precision)
    x = x + matmul(o, a["out"]["kernel"], precision)
    u = rms_norm(x, w["norm2"]["scale"], eps)
    experts, gates = route(u, w["moe"]["router"], c, precision)
    return x + experts_held(u, experts, gates, w["moe"], c, precision), kl, pairs


def forward_one(params: dict, tokens, c: dict, precision: str = "f32"):
    """tokens: (s,) of one sequence -> ((s, vocab) float32 logits, the mean
    over the layers of the summed KL, the pairs selected a layer)."""
    x = params["embed"].astype(jnp.float32)[tokens]
    kls, pairs = [], []
    for i in range(c["num_hidden_layers"]):
        x, kl, n = jax.checkpoint(lambda x, w: layer(x, w, c, precision))(
            x, params[f"block{i}"])
        kls.append(kl)
        pairs.append(n)
    logits = matmul(rms_norm(x, params["norm_f"]["scale"], c["rms_norm_eps"]),
                    params["lm_head"]["kernel"], precision)
    return logits, sum(kls) / len(kls), jnp.stack(pairs)


def logits_one(params: dict, tokens, c: dict, precision: str = "f32"):
    return forward_one(params, tokens, c, precision)[0]


def loss_rows(params: dict, batch, c: dict, precision: str = "f32"):
    """Summed over the rows of `batch` = (tokens (n, s), labels (n, s)): the
    next-token cross-entropy of every token, over the configuration's
    vocabulary (its slice), plus `index_loss_weight` times the mean over the
    layers of every token's KL; the caller divides by the batch's tokens."""
    tokens, labels = batch

    def row(tl):
        t, l = tl
        lg, kl, _ = forward_one(params, t, c, precision)
        lse = jax.nn.logsumexp(lg, -1)
        return (jnp.sum(lse - jnp.take_along_axis(lg, l[:, None], -1)[:, 0])
                + c["index_loss_weight"] * kl)

    return jnp.sum(jax.lax.map(row, (tokens, labels)))


def units(batch) -> int:
    """What the loss is a mean over: tokens."""
    return batch[0].shape[0] * batch[0].shape[1]
