"""SmallThinker-family decoder (a head size of its own, full NoPE layers
among rotary window layers, every layer a mixture of gated ReLU experts with
the router on the attention's input, of which this chip holds a range): the
program's model for a configuration, its training FLOPs, and the grouped
products' operations and bytes, from the shapes alone. Recompute (remat) is
never counted; of the attention only the pairs a query may see; of the
experts only what is HERE, at the rows a uniform router sends."""

from __future__ import annotations

from perfbench import flops


def held(c: dict) -> tuple[int, int]:
    return (c.get("moe_experts_first", 0),
            c.get("moe_num_primary_experts_held", c["moe_num_primary_experts"]))


def pattern(c: dict) -> tuple[tuple[bool, bool], ...]:
    """(takes the window, takes the rotary) a layer, over the depth run."""
    n = c["num_hidden_layers"]
    return tuple((bool(w), bool(r)) for w, r in
                 zip(c["sliding_window_layout"][:n], c["rope_layout"][:n]))


def build(cfg: dict, cell: dict):
    import jax.numpy as jnp

    from tpunet.models import Transformer

    return Transformer(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["moe_ffn_hidden_size"], n_experts=cfg["moe_num_primary_experts"],
        moe_every=1, moe_top_k=cfg["moe_num_active_primary_experts"],
        moe_impl="grouped", moe_held=held(cfg),
        attn_window=cfg["sliding_window_size"], attn_pattern=pattern(cfg),
        attn_impl="flash", rope_theta=float(cfg["rope_theta"]),
        norm_eps=cfg["rms_norm_eps"],
        compute_dtype=jnp.dtype(cfg["compute_dtype"]),
        remat=bool(cell.get("remat", False)))


# -- attention, a layer by its kind ------------------------------------------------

def layer_windows(c: dict) -> list:
    """The window of each layer that is run, None where it sees all."""
    return [c["sliding_window_size"] if w else None for w, _ in pattern(c)]


def attention_flops_fwd(c: dict, batch: int, seq: int, window) -> float:
    """QK^T and PV of ONE layer of that window, forward, useful part only."""
    width = c["num_attention_heads"] * c["head_dim"]
    return 4.0 * width * flops.mean_keys(seq, window) * batch * seq


# -- the grouped products ------------------------------------------------------------

def expected_rows(c: dict, tokens: int) -> float:
    """(token, choice) pairs that fall on the held experts when every
    expert is as likely as another: an expectation, not a count."""
    return (tokens * c["moe_num_active_primary_experts"] * held(c)[1]
            / c["moe_num_primary_experts"])


def gmm_flops(rows: float, k: int, n: int) -> float:
    """One grouped product over `rows` rows in all, (rows, k) x (k, n) a
    group; the same count for the product against the transposed matrices
    and for the matrices' gradient."""
    return 2.0 * rows * k * n


def gmm_bytes(rows: float, groups: int, k: int, n: int, itemsize: int = 2,
              out_itemsize: int = 2) -> float:
    """Least HBM traffic of one grouped product: the rows read, every
    group's matrix read once, the result written."""
    return rows * k * itemsize + groups * k * n * itemsize + rows * n * out_itemsize


def tgmm_bytes(rows: float, groups: int, k: int, n: int, itemsize: int = 2) -> float:
    """Of the matrices' gradient: both row operands read, a float32 matrix a
    group written."""
    return rows * (k + n) * itemsize + groups * k * n * 4


def layer_gmm(c: dict, tokens: int) -> dict:
    """{"fwd", "bwd"}: (FLOPs, bytes) of one layer's grouped products in
    one pass of that direction: forward gate, up, down; backward the three
    against the transposed matrices and the three matrices' gradients."""
    rows, groups = expected_rows(c, tokens), held(c)[1]
    d, f = c["hidden_size"], c["moe_ffn_hidden_size"]
    one = gmm_flops(rows, d, f)
    fwd_b = 2 * gmm_bytes(rows, groups, d, f) + gmm_bytes(rows, groups, f, d)
    bwd_b = fwd_b + 2 * tgmm_bytes(rows, groups, d, f) + tgmm_bytes(rows, groups, f, d)
    return {"fwd": (3 * one, fwd_b), "bwd": (6 * one, bwd_b)}


# -- the step ----------------------------------------------------------------------

def layer_params(c: dict) -> int:
    d, h, kv, dh = (c["hidden_size"], c["num_attention_heads"],
                    c["num_key_value_heads"], c["head_dim"])
    attn = 2 * d * h * dh + 2 * d * kv * dh
    experts = held(c)[1] * 3 * d * c["moe_ffn_hidden_size"]
    return attn + d * c["moe_num_primary_experts"] + experts + 2 * d


def params(c: dict) -> int:
    """Parameters HERE: the held experts, the vocabulary's slice."""
    d, v = c["hidden_size"], c["vocab_size"]
    return c["num_hidden_layers"] * layer_params(c) + 2 * v * d + d


def train_flops(cfg: dict, mix: dict) -> float:
    """Model FLOPs of forward and backward of what is here: projections,
    router, the experts at the expected rows, attention's useful scores by
    each layer's kind, the head over the vocabulary's slice."""
    b, s = mix["batch"], mix["seq"]
    tokens = b * s
    d, h, kv, dh = (cfg["hidden_size"], cfg["num_attention_heads"],
                    cfg["num_key_value_heads"], cfg["head_dim"])
    dense = 2 * d * h * dh + 2 * d * kv * dh + d * cfg["moe_num_primary_experts"]
    per_layer = 6.0 * dense * tokens + 3.0 * layer_gmm(cfg, tokens)["fwd"][0]
    attn = sum(3.0 * attention_flops_fwd(cfg, b, s, w) for w in layer_windows(cfg))
    return (cfg["num_hidden_layers"] * per_layer + attn
            + 6.0 * cfg["vocab_size"] * d * tokens)
