"""EvaByte-family decoder (EVA chunk-summary attention, byte vocabulary,
several prediction heads, float32 residual stream): the program's model for
a configuration, and its training FLOPs and its attention's operations and
bytes from the shapes alone. Recompute (remat) is never counted, and of the
attention only the pairs a query may see."""

from __future__ import annotations


def build(cfg: dict, cell: dict):
    import jax.numpy as jnp

    from tpunet.models import Transformer

    if cfg["num_key_value_heads"] != cfg["num_attention_heads"]:
        raise SystemExit("EVA attention takes as many key heads as query heads")
    return Transformer(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"], n_heads=cfg["num_attention_heads"],
        d_ff=cfg["intermediate_size"], mlp_impl="swiglu", attn_impl="eva",
        eva_window=cfg["window_size"], eva_chunk=cfg["chunk_size"],
        norm_eps=cfg["rms_norm_eps"], norm_unit_offset=cfg["norm_add_unit_offset"],
        rope_theta=float(cfg["rope_theta"]),
        residual_dtype=jnp.float32 if cfg["fp32_skip_add"] else None,
        n_pred_heads=cfg["num_pred_heads"],
        compute_dtype=jnp.dtype(cfg["compute_dtype"]),
        remat=bool(cell.get("remat", False)))


# -- what a query sees -----------------------------------------------------------

def visible_pairs(seq: int, window: int, chunk: int) -> tuple[int, int]:
    """(query-key pairs inside the windows, query-summary pairs) of one
    sequence: position t sees the t % window + 1 keys of its window up to
    itself and the window / chunk summaries of each earlier window."""
    full, rest = divmod(seq, window)
    local = full * window * (window + 1) // 2 + rest * (rest + 1) // 2
    per_window = window // chunk
    remote = per_window * (window * full * (full - 1) // 2 + rest * full)
    return local, remote


def attention_flops_fwd(c: dict, batch: int, seq: int) -> float:
    """QK^T and PV of one layer's forward over the visible pairs, both key
    sets: 4 x hidden_size a pair."""
    local, remote = visible_pairs(seq, c["window_size"], c["chunk_size"])
    return 4.0 * c["hidden_size"] * (local + remote) * batch


def summarize_flops_fwd(c: dict, batch: int, seq: int) -> float:
    """phi . k, pi-weighted sums of k and of v: 6 a key element."""
    return 6.0 * c["hidden_size"] * batch * seq


def attention_bytes_fwd(c: dict, batch: int, seq: int, itemsize: int = 2) -> float:
    """Least HBM traffic of the kernels of one layer's forward: read q, k, v
    and the summaries of k and v, write o."""
    rows = seq * 4 + 2 * (seq // c["chunk_size"])
    return float(batch * rows * c["hidden_size"] * itemsize)


def attention_bytes_bwd(c: dict, batch: int, seq: int, itemsize: int = 2) -> float:
    """Backward: read q, k, v, o, do and the summaries; write dq, dk, dv and
    the summaries' gradients."""
    rows = seq * 8 + 4 * (seq // c["chunk_size"])
    return float(batch * rows * c["hidden_size"] * itemsize)


# -- the step ----------------------------------------------------------------------

def layer_params(c: dict) -> int:
    d, ff = c["hidden_size"], c["intermediate_size"]
    return 4 * d * d + 3 * d * ff + 2 * d + 2 * d  # norms; phi and mu


def params(c: dict) -> int:
    d, v = c["hidden_size"], c["vocab_size"]
    return (c["num_hidden_layers"] * layer_params(c)
            + v * d + c["num_pred_heads"] * v * d + d)


def matmul_params(c: dict) -> int:
    """Parameters a token is multiplied with: the projections, the MLP and
    the head of all prediction heads."""
    d, ff = c["hidden_size"], c["intermediate_size"]
    return (c["num_hidden_layers"] * (4 * d * d + 3 * d * ff)
            + c["num_pred_heads"] * c["vocab_size"] * d)


def train_flops(cfg: dict, mix: dict) -> float:
    b, s = mix["batch"], mix["seq"]
    per_layer = 3.0 * (attention_flops_fwd(cfg, b, s) + summarize_flops_fwd(cfg, b, s))
    return 6.0 * matmul_params(cfg) * b * s + cfg["num_hidden_layers"] * per_layer
