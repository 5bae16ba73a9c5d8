"""Mistral-family decoder (GQA, SwiGLU, sliding window, untied head, no
bias): the program's model for a configuration, and its parameters and
training FLOPs from the shapes alone."""

from __future__ import annotations

from perfbench import flops


def build(cfg: dict, cell: dict):
    import jax.numpy as jnp

    from tpunet.models import Transformer

    if cfg["hidden_size"] != cfg["num_attention_heads"] * cfg["head_dim"]:
        raise SystemExit("the program derives head_dim from d_model / n_heads")
    if cfg["rope_theta"] != 10000.0:
        raise SystemExit("the program's rotary base is fixed at 10000")
    if cfg["rms_norm_eps"] != 1e-6:
        raise SystemExit("the program's RMSNorm epsilon is fixed at 1e-6")
    return Transformer(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"], n_heads=cfg["num_attention_heads"],
        d_ff=cfg["intermediate_size"], n_kv_heads=cfg["num_key_value_heads"],
        mlp_impl="swiglu", attn_window=cfg["sliding_window"],
        attn_impl="flash", compute_dtype=jnp.dtype(cfg["compute_dtype"]),
        remat=bool(cell.get("remat", False)))


def layer_params(c: dict) -> int:
    d, h, kv, dh, ff = (c["hidden_size"], c["num_attention_heads"],
                        c["num_key_value_heads"], c["head_dim"],
                        c["intermediate_size"])
    attn = d * h * dh + 2 * d * kv * dh + h * dh * d
    return attn + 3 * d * ff + 2 * d


def params(c: dict) -> int:
    d, v = c["hidden_size"], c["vocab_size"]
    return (c["num_hidden_layers"] * layer_params(c)
            + 2 * v * d + d)  # embedding, untied head, final norm


def matmul_params(c: dict) -> int:
    """Parameters a token is multiplied with: all but the embedding (a
    lookup) and the norm scales."""
    d = c["hidden_size"]
    return (c["num_hidden_layers"] * (layer_params(c) - 2 * d)
            + c["vocab_size"] * d)


def train_flops_per_token(c: dict, seq: int) -> float:
    attn = 3 * flops.attention_flops_fwd(c, 1, seq) / seq * c["num_hidden_layers"]
    return 6.0 * matmul_params(c) + attn


def train_flops(cfg: dict, mix: dict) -> float:
    return train_flops_per_token(cfg, mix["seq"]) * mix["batch"] * mix["seq"]
