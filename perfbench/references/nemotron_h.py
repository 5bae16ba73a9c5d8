"""NVIDIA Nemotron-H (Nemotron-3-Super-120B-A12B's config.json; the family:
arXiv:2504.03624) as a plain reference: blocks of ONE sublayer each, by the
characters of `hybrid_override_pattern`, pre-norm, RMSNorm (`norm_eps`),
untied head, no biases but the conv's. For one sequence x (s, d):

    block:     x = x + f(rms_norm(x))
    "M":       [z | xBC | dt] = h W_in;  xBC = silu(causal conv(xBC) + b_conv)
               [x~ | B | C] = xBC;  delta = softplus(dt + dt_bias);  A = -exp(A_log)
               S_t = exp(delta_t A) S_{t-1} + delta_t x~_t B_t^T;  y_t = S_t C_t + D x~_t
               y = rms_norm over each group's channels of (y * silu(z)), times w
               out = y W_out
    "*":       causal grouped-query attention, no position embedding
    "E":       s = sigmoid(h W_r);  chosen = top-k of (s + b_corr)
               g_e = routed_scaling_factor * s_e / sum over chosen of s
               out = W_up(sum over chosen HELD e of g_e W2_e relu(W1_e (h W_down))^2)
                     + W2_s relu(W1_s h)^2
    MTP:       m = W_eh [rms_norm_e(Emb(t_{i+1})); rms_norm_h(x_last)]
               blocks of `mtp_hybrid_override_pattern`, rms_norm_f, the shared head

The loss of a sequence is the mean next-token cross-entropy plus
`mtp_loss_weight` times the MTP module's mean cross-entropy against the
token two ahead, over the positions where that lies in the sequence.

This chip's share stands in the configuration (heads of the mixers, the
experts and the shared expert's columns held, a slice of the vocabulary);
what the absent shares would add is left out, here as in the program.

Independent of tpunet: no kernel, no chunks, no cache. The state-space
recurrence is a `lax.scan` over tokens, one token a step; its backward
recomputes the states between every REMAT_EVERY-th one (the values are the
plain scan's, only what is kept in memory differs). The experts are dense
products over every token, an expert at a time, each weighed by the
token's gate for it (0 where the token did not choose it). Parameter paths
are the program's, so that one spec serves both.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from perfbench.references.mistral import attention, rms_norm
from perfbench.references.precision import einsum, matmul

REMAT_EVERY = 64  # tokens between the states the scan's backward keeps


def _std(c: dict) -> float:
    return c["initializer_range"]


def _mamba_spec(c: dict, p: str) -> dict:
    d, h, hd = c["hidden_size"], c["mamba_num_heads"], c["mamba_head_dim"]
    gn = c["n_groups"] * c["ssm_state_size"]
    inner, conv = h * hd, h * hd + 2 * gn
    return {f"{p}/in_proj/kernel": ((d, 2 * inner + 2 * gn + h), _std(c)),
            f"{p}/conv_kernel": ((c["conv_kernel"], conv), c["conv_initializer_range"]),
            f"{p}/conv_bias": ((conv,), c["conv_initializer_range"]),
            f"{p}/dt_bias": ((h,), c["dt_bias_initializer_range"]),
            f"{p}/A_log": ((h,), c["A_log_initializer_range"]),
            f"{p}/D": ((h,), None),
            f"{p}/norm_scale": ((inner,), None),
            f"{p}/out_proj/kernel": ((inner, d), _std(c))}


def _attn_spec(c: dict, p: str) -> dict:
    d, h, kv, dh = (c["hidden_size"], c["num_attention_heads"],
                    c["num_key_value_heads"], c["head_dim"])
    return {f"{p}/q/kernel": ((d, h * dh), _std(c)),
            f"{p}/k/kernel": ((d, kv * dh), _std(c)),
            f"{p}/v/kernel": ((d, kv * dh), _std(c)),
            f"{p}/out/kernel": ((h * dh, d), _std(c))}


def _moe_spec(c: dict, p: str) -> dict:
    d, lat, f = c["hidden_size"], c["moe_latent_size"], c["moe_intermediate_size"]
    held, fs = c["n_routed_experts_held"], c["moe_shared_expert_columns_held"]
    return {f"{p}/router": ((d, c["n_routed_experts"]), _std(c)),
            f"{p}/router_bias": ((c["n_routed_experts"],), 0),
            f"{p}/up": ((held, lat, f), _std(c)),
            f"{p}/down": ((held, f, lat), _std(c)),
            f"{p}/to_latent": ((d, lat), _std(c)),
            f"{p}/from_latent": ((lat, d), _std(c)),
            f"{p}/shared_up": ((d, fs), _std(c)),
            f"{p}/shared_down": ((fs, d), _std(c))}


_SUBLAYER = {"M": ("mamba", _mamba_spec), "*": ("attn", _attn_spec), "E": ("moe", _moe_spec)}


def block_spec(c: dict, name: str, kind: str) -> dict:
    sub, spec = _SUBLAYER[kind]
    return {f"{name}/norm1/scale": ((c["hidden_size"],), None), **spec(c, f"{name}/{sub}")}


def pattern(c: dict) -> str:
    return c["hybrid_override_pattern"][: c["num_hidden_layers"]]


def mtp_pattern(c: dict) -> str:
    return c["mtp_hybrid_override_pattern"] * c["num_nextn_predict_layers"]


def param_spec(c: dict) -> dict:
    d, v = c["hidden_size"], c["vocab_size"]
    spec = {"embed": ((v, d), c["embed_initializer_range"]),
            "norm_f/scale": ((d,), None),
            "lm_head/kernel": ((d, v), _std(c))}
    for i, kind in enumerate(pattern(c)):
        spec.update(block_spec(c, f"block{i}", kind))
    spec.update({"mtp_proj/kernel": ((2 * d, d), _std(c)),
                 "mtp_norm_e/scale": ((d,), None), "mtp_norm_h/scale": ((d,), None),
                 "mtp_norm_f/scale": ((d,), None)})
    for j, kind in enumerate(mtp_pattern(c)):
        spec.update(block_spec(c, f"mtp_block{j}", kind))
    return spec


# -- the sublayers, one sequence -----------------------------------------------

def ssm_scan(xs, delta, a, b, cm, precision: str):
    """xs: (s, H, P); delta: (s, H); a: (H,); b, cm: (s, H, N), each head's
    group already picked. -> y (s, H, P), token by token."""
    def step(state, inp):
        x_t, d_t, b_t, c_t = inp
        state = (jnp.exp(d_t * a)[:, None, None] * state
                 + einsum("hp,hn->hpn", d_t[:, None] * x_t, b_t, precision))
        return state, einsum("hpn,hn->hp", state, c_t, precision)

    @jax.checkpoint
    def span(state, inp):
        return jax.lax.scan(step, state, inp)

    s, h, p = xs.shape
    n = b.shape[-1]
    every = next(k for k in range(min(REMAT_EVERY, s), 0, -1) if s % k == 0)
    cut = lambda t: t.reshape(s // every, every, *t.shape[1:])  # noqa: E731
    _, y = jax.lax.scan(span, jnp.zeros((h, p, n), jnp.float32),
                        (cut(xs), cut(delta), cut(b), cut(cm)))
    return y.reshape(s, h, p)


def mamba(h, w: dict, c: dict, precision: str):
    s = h.shape[0]
    heads, hd, g, n, k = (c["mamba_num_heads"], c["mamba_head_dim"], c["n_groups"],
                          c["ssm_state_size"], c["conv_kernel"])
    inner = heads * hd
    zxbc = matmul(h, w["in_proj"]["kernel"], precision)
    z, xbc, dt = jnp.split(zxbc, [inner, 2 * inner + 2 * g * n], axis=-1)
    padded = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1])), xbc])
    conv = sum(padded[i:i + s] * w["conv_kernel"][i] for i in range(k)) + w["conv_bias"]
    xs, b, cm = jnp.split(jax.nn.silu(conv), [inner, inner + g * n], axis=-1)
    delta = jax.nn.softplus(dt + w["dt_bias"])
    of_head = lambda t: jnp.repeat(t.reshape(s, g, n), heads // g, axis=1)  # noqa: E731
    xs = xs.reshape(s, heads, hd)
    y = ssm_scan(xs, delta, -jnp.exp(w["A_log"]), of_head(b), of_head(cm), precision)
    y = (y + w["D"][:, None] * xs).reshape(s, inner) * jax.nn.silu(z)
    y = y.reshape(s, g, inner // g)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + c["norm_eps"])
    return matmul(y.reshape(s, inner) * w["norm_scale"], w["out_proj"]["kernel"], precision)


def self_attention(h, w: dict, c: dict, precision: str):
    s = h.shape[0]
    hq, kv, dh = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    q = matmul(h, w["q"]["kernel"], precision).reshape(s, hq, dh)
    k = matmul(h, w["k"]["kernel"], precision).reshape(s, kv, dh)
    v = matmul(h, w["v"]["kernel"], precision).reshape(s, kv, dh)
    return matmul(attention(q, k, v, None, precision), w["out"]["kernel"], precision)


def route(h, w: dict, c: dict, precision: str):
    """(chosen experts (s, k), their gates (s, k)) of one sequence."""
    scores = jax.nn.sigmoid(matmul(h, w["router"], precision))
    _, experts = jax.lax.top_k(scores + w["router_bias"], c["num_experts_per_tok"])
    top = jnp.take_along_axis(scores, experts, axis=-1)
    return experts, c["routed_scaling_factor"] * top / jnp.sum(top, -1, keepdims=True)


def relu2(x, up, down, precision: str):
    return matmul(jnp.square(jax.nn.relu(matmul(x, up, precision))), down, precision)


def latent_moe(h, w: dict, c: dict, precision: str):
    experts, gates = route(h, w, c, precision)
    u = matmul(h, w["to_latent"], precision)
    first = c.get("n_routed_experts_first", 0)

    def one(out, args):
        e, up, down = args
        weight = jnp.sum(jnp.where(experts == e, gates, 0.0), axis=-1)
        return out + weight[:, None] * relu2(u, up, down, precision), None

    ids = first + jnp.arange(c["n_routed_experts_held"])
    routed = jax.lax.scan(one, jnp.zeros_like(u), (ids, w["up"], w["down"]))[0]
    return (matmul(routed, w["from_latent"], precision)
            + relu2(h, w["shared_up"], w["shared_down"], precision))


_FORWARD = {"M": ("mamba", mamba), "*": ("attn", self_attention), "E": ("moe", latent_moe)}


def block(x, w: dict, kind: str, c: dict, precision: str):
    sub, f = _FORWARD[kind]
    return x + f(rms_norm(x, w["norm1"]["scale"], c["norm_eps"]), w[sub], c, precision)


def _blocks(x, params: dict, prefix: str, kinds: str, c: dict, precision: str):
    for i, kind in enumerate(kinds):
        x = jax.checkpoint(lambda x, w, kind=kind: block(x, w, kind, c, precision))(
            x, params[f"{prefix}{i}"])
    return x


def forward_one(params: dict, tokens, c: dict, precision: str = "f32"):
    """tokens: (s,) -> ((s, vocab) logits, (s, vocab) the MTP module's)."""
    eps, emb = c["norm_eps"], params["embed"].astype(jnp.float32)
    head = lambda x, scale: matmul(rms_norm(x, scale, eps),  # noqa: E731
                                   params["lm_head"]["kernel"], precision)
    last = _blocks(emb[tokens], params, "block", pattern(c), c, precision)
    following = emb[jnp.roll(tokens, -1)]
    m = matmul(jnp.concatenate([rms_norm(following, params["mtp_norm_e"]["scale"], eps),
                                rms_norm(last, params["mtp_norm_h"]["scale"], eps)], -1),
               params["mtp_proj"]["kernel"], precision)
    m = _blocks(m, params, "mtp_block", mtp_pattern(c), c, precision)
    return head(last, params["norm_f"]["scale"]), head(m, params["mtp_norm_f"]["scale"])


def logits_one(params: dict, tokens, c: dict, precision: str = "f32"):
    return forward_one(params, tokens, c, precision)[0]


def _nll(logits, targets):
    return (jax.nn.logsumexp(logits, -1)
            - jnp.take_along_axis(logits, targets[:, None], -1)[:, 0])


def loss_rows(params: dict, batch, c: dict, precision: str = "f32"):
    """Summed over the rows of `batch` = (tokens (n, s), labels (n, s)): the
    next-token cross-entropy of every token plus mtp_loss_weight times the
    MTP module's, the latter scaled by s / (s - 2) so that divided by the
    batch's tokens it is the mean over the positions it is taken at."""
    tokens, labels = batch
    s = tokens.shape[1]
    inside = jnp.arange(s) < s - 2

    def row(tl):
        t, l = tl
        logits, mtp = forward_one(params, t, c, precision)
        extra = jnp.sum(jnp.where(inside, _nll(mtp, jnp.roll(t, -2)), 0.0))
        return jnp.sum(_nll(logits, l)) + c["mtp_loss_weight"] * extra * s / (s - 2)

    return jnp.sum(jax.lax.map(row, (tokens, labels)))


def units(batch) -> int:
    """What the loss is a mean over: tokens."""
    return batch[0].shape[0] * batch[0].shape[1]
