"""Keye-VL-2.0's language model (grouped-query attention whose keys an
indexer selects, a norm a head on q and k, every layer a mixture of SwiGLU
experts with the router on the expert layer's own input, of which this chip
holds a range): the program's model for a configuration, its training
FLOPs, and the operations and bytes of the selecting attention's kernels
and of the grouped products, from the shapes alone. Recompute (remat) is
never counted; of the attention only the pairs a query SELECTS; of the
indexer and of its loss's pass the causal pairs (every earlier key is
scored); of the experts only what is HERE, at the rows a uniform router
sends."""

from __future__ import annotations

from perfbench import flops
from perfbench.models.smallthinker import gmm_bytes, gmm_flops, tgmm_bytes

__all__ = ["build", "train_flops", "layer_gmm", "layer_dsa", "gmm_flops", "gmm_bytes"]


def held(c: dict) -> tuple[int, int]:
    return c.get("experts_first", 0), c.get("num_local_experts", c["num_experts"])


def build(cfg: dict, cell: dict):
    import jax.numpy as jnp

    from tpunet.models import Transformer

    sa = cfg["sa_config"]
    if sa["indexer_num_kv_heads"] != 1 or cfg["decoder_sparse_step"] != 1:
        raise SystemExit("the program's indexer has one key head, and every "
                         "layer of this family is an expert layer")
    return Transformer(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["moe_intermediate_size"], n_experts=cfg["num_experts"],
        moe_every=1, moe_top_k=cfg["num_experts_per_tok"], moe_impl="grouped",
        moe_held=held(cfg), moe_activation=cfg["hidden_act"],
        moe_router_input="mlp_input", qk_norm=True,
        attn_select_top_k=sa["topk"], attn_index_heads=sa["indexer_num_heads"],
        attn_index_head_dim=sa["indexer_head_dim"],
        index_loss_weight=cfg["index_loss_weight"], attn_impl="flash",
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        compute_dtype=jnp.dtype(cfg["compute_dtype"]),
        remat=bool(cell.get("remat", False)))


# -- the selecting attention ---------------------------------------------------------

def causal_pairs(seq: int) -> float:
    """(query, key) pairs of one row with the key at or before the query."""
    return seq * (seq + 1) / 2


def selected_pairs(seq: int, top_k: int) -> float:
    """sum_t min(t + 1, top_k): the pairs one row's queries keep."""
    k = min(top_k, seq)
    return k * (k + 1) / 2 + (seq - k) * k


def layer_dsa(c: dict, batch: int, seq: int) -> dict:
    """{"index", "attn_fwd", "attn_bwd"}: (FLOPs, bytes) of one layer's
    kernels of that kind in one pass: the LEAST work, whatever implements
    them. The indexer's scores: a product of 2 x heads x head size a causal
    pair, qI, kI and w read, a float32 score a causal pair written. The
    attention: QK^T and PV over the SELECTED pairs alone (a kernel that
    visits every causal tile and masks inside it does 2.3 times that at
    8,192 and reads low), q, k, v read and o written, backward the flash
    convention's 2.5 times (dq, dk, dv and the scores once more) over q, k,
    v, o, do read and dq, dk, dv written."""
    sa = c["sa_config"]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    index_f = 2.0 * hi * di * causal_pairs(seq) * batch
    index_b = batch * (seq * (hi * di + di) * 2 + seq * hi * 4 + causal_pairs(seq) * 4)
    width = c["num_attention_heads"] * c["head_dim"]
    attn_f = 4.0 * width * selected_pairs(seq, sa["topk"]) * batch
    return {"index": (index_f, index_b),
            "attn_fwd": (attn_f, flops.flash_bytes_fwd(c, batch, seq)),
            "attn_bwd": (2.5 * attn_f, flops.flash_bytes_bwd(c, batch, seq))}


# -- the grouped products ------------------------------------------------------------

def expected_rows(c: dict, tokens: int) -> float:
    """(token, choice) pairs that fall on the held experts when every
    expert is as likely as another: an expectation, not a count."""
    return tokens * c["num_experts_per_tok"] * held(c)[1] / c["num_experts"]


def layer_gmm(c: dict, tokens: int) -> dict:
    """{"fwd", "bwd"}: (FLOPs, bytes) of one layer's grouped products in
    one pass of that direction: forward gate, up, down; backward the three
    against the transposed matrices and the three matrices' gradients."""
    rows, groups = expected_rows(c, tokens), held(c)[1]
    d, f = c["hidden_size"], c["moe_intermediate_size"]
    one = gmm_flops(rows, d, f)
    fwd_b = 2 * gmm_bytes(rows, groups, d, f) + gmm_bytes(rows, groups, f, d)
    bwd_b = fwd_b + 2 * tgmm_bytes(rows, groups, d, f) + tgmm_bytes(rows, groups, f, d)
    return {"fwd": (3 * one, fwd_b), "bwd": (6 * one, bwd_b)}


# -- the step ----------------------------------------------------------------------

def _projections(c: dict) -> tuple[int, int]:
    """(weights a token is multiplied with whose input takes a gradient,
    the indexer's, whose input is detached) of one layer."""
    d, h, kv, dh = (c["hidden_size"], c["num_attention_heads"],
                    c["num_key_value_heads"], c["head_dim"])
    sa = c["sa_config"]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    return (2 * d * h * dh + 2 * d * kv * dh + d * c["num_experts"],
            d * (hi * di + di + hi))


def layer_params(c: dict) -> int:
    main, index = _projections(c)
    norms = 2 * c["hidden_size"] + 2 * c["head_dim"] + 2 * c["sa_config"]["indexer_head_dim"]
    experts = held(c)[1] * 3 * c["hidden_size"] * c["moe_intermediate_size"]
    return main + index + experts + norms


def params(c: dict) -> int:
    """Parameters HERE: the held experts, the vocabulary's slice."""
    d, v = c["hidden_size"], c["vocab_size"]
    return c["num_hidden_layers"] * layer_params(c) + 2 * v * d + d


def train_flops(cfg: dict, mix: dict) -> float:
    """Model FLOPs of forward and backward of what is here. Projections and
    router 6 a weight a token; the indexer's projections 4 (their input is
    detached: no gradient to it); the experts at the expected rows; the
    attention's QK^T and PV over the SELECTED pairs, 3 times forward; the
    indexer's scores over the causal pairs, forward and the two products of
    its loss's backward; the loss's pass, the main attention's QK^T once more
    over the causal pairs; the head over the vocabulary's slice."""
    b, s = mix["batch"], mix["seq"]
    tokens = b * s
    main, index = _projections(cfg)
    dsa = layer_dsa(cfg, b, s)
    width = cfg["num_attention_heads"] * cfg["head_dim"]
    per_layer = (6.0 * main * tokens + 4.0 * index * tokens
                 + 3.0 * layer_gmm(cfg, tokens)["fwd"][0]
                 + 3.0 * dsa["attn_fwd"][0] + 3.0 * dsa["index"][0]
                 + 2.0 * width * causal_pairs(s) * b)
    return (cfg["num_hidden_layers"] * per_layer
            + 6.0 * cfg["vocab_size"] * cfg["hidden_size"] * tokens)
