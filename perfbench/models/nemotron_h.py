"""Nemotron-H (blocks of one sublayer by `hybrid_override_pattern`: Mamba-2
mixers over a chunked state-space scan, latent mixtures of relu2 experts
with a sigmoid router and a shared expert, NoPE grouped-query attention, a
multi-token-prediction module on the shared head; this chip holds a share
of the heads, the experts, the shared expert's columns and the vocabulary):
the program's model for a configuration, its training FLOPs, and the
operations and bytes of the scan and of the latent grouped products, from
the shapes alone. Recompute (remat) is never counted; of the attention only
the causal pairs; of the experts only what is HERE, at the rows a uniform
router sends; of the scan the least work of the chunked algorithm at the
configuration's chunk, whatever implements it."""

from __future__ import annotations

from perfbench import flops
from perfbench.models.smallthinker import gmm_bytes, gmm_flops, tgmm_bytes

SUPPORTED = {"mamba_hidden_act": "silu", "mlp_hidden_act": "relu2", "use_conv_bias": True,
             "mamba_proj_bias": False, "attention_bias": False, "mlp_bias": False,
             "n_group": 1, "topk_group": 1, "norm_topk_prob": True, "n_shared_experts": 1,
             "tie_word_embeddings": False}


def pattern(c: dict) -> str:
    return c["hybrid_override_pattern"][: c["num_hidden_layers"]]


def mtp_pattern(c: dict) -> str:
    return c["mtp_hybrid_override_pattern"] * c["num_nextn_predict_layers"]


def layers_of(c: dict, kind: str) -> int:
    """Blocks of that kind that run a step, the MTP module's included."""
    return (pattern(c) + mtp_pattern(c)).count(kind)


def held(c: dict) -> tuple[int, int]:
    return c.get("n_routed_experts_first", 0), c["n_routed_experts_held"]


def build(cfg: dict, cell: dict):
    import jax.numpy as jnp

    from tpunet.models import Transformer

    off = {k: cfg[k] for k, v in SUPPORTED.items() if cfg[k] != v}
    if off or cfg["num_nextn_predict_layers"] != 1:
        raise SystemExit(f"the program runs this family with {SUPPORTED} and one "
                         f"MTP module; the configuration has {off}")
    return Transformer(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"], layer_pattern=pattern(cfg),
        mtp_pattern=mtp_pattern(cfg), mtp_loss_weight=cfg["mtp_loss_weight"],
        n_heads=cfg["num_attention_heads"], n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], attn_pattern=((False, False),), attn_impl="flash",
        mamba_heads=cfg["mamba_num_heads"], mamba_head_dim=cfg["mamba_head_dim"],
        mamba_groups=cfg["n_groups"], mamba_state=cfg["ssm_state_size"],
        mamba_conv=cfg["conv_kernel"], mamba_chunk=cfg["chunk_size"],
        d_ff=cfg["moe_intermediate_size"], n_experts=cfg["n_routed_experts"],
        moe_top_k=cfg["num_experts_per_tok"], moe_impl="grouped", moe_held=held(cfg),
        moe_activation="relu2", moe_scoring="sigmoid",
        moe_routed_scale=float(cfg["routed_scaling_factor"]),
        moe_latent=cfg["moe_latent_size"],
        moe_shared_d_ff=cfg["moe_shared_expert_columns_held"],
        norm_eps=cfg["norm_eps"], compute_dtype=jnp.dtype(cfg["compute_dtype"]),
        remat=bool(cell.get("remat", False)))


# -- the scan ------------------------------------------------------------------

def layer_ssd(c: dict, batch: int, seq: int, itemsize: int = 2) -> dict:
    """{"fwd", "bwd"}: (FLOPs, bytes) of one layer's scan in one pass, at
    the configuration's chunk Q: the least work of the chunked algorithm.
    A chunk of a head, forward: C B^T and its product with dt x over the
    causal pairs ((N + P) Q (Q + 1)), the entering state's part of y and the
    leaving state (2 Q N P each). Backward: the scores once more, dt x's,
    B's and C's gradients over the causal pairs and the scores' gradient
    ((3 N + 2 P) Q (Q + 1)), and five products with a state (10 Q N P).
    Bytes: dt x, B and C (a group's once), the decays (float32) and y,
    forward; backward those, dy, and the four gradients."""
    q, h, p, n = c["chunk_size"], c["mamba_num_heads"], c["mamba_head_dim"], c["ssm_state_size"]
    chunks = batch * h * -(-seq // q)
    pairs = q * (q + 1)
    rows = batch * seq
    u = rows * h * p * itemsize
    bc = 2 * rows * c["n_groups"] * n * itemsize
    cs = rows * h * 4
    return {"fwd": (chunks * ((n + p) * pairs + 4 * q * n * p), 2 * u + bc + cs),
            "bwd": (chunks * ((3 * n + 2 * p) * pairs + 10 * q * n * p),
                    3 * u + 2 * bc + 2 * cs)}


# -- the latent grouped products -------------------------------------------------

def expected_rows(c: dict, tokens: int) -> float:
    """(token, choice) pairs that fall on the held experts when every
    expert is as likely as another: an expectation, not a count."""
    return tokens * c["num_experts_per_tok"] * held(c)[1] / c["n_routed_experts"]


def layer_latent_gmm(c: dict, batch: int, seq: int) -> dict:
    """{"fwd", "bwd"}: (FLOPs, bytes) of one layer's grouped products in
    one pass: forward up and down in the latent width (two products, relu2
    is not gated); backward both against the transposed matrices and both
    matrices' gradients."""
    rows, groups = expected_rows(c, batch * seq), held(c)[1]
    lat, f = c["moe_latent_size"], c["moe_intermediate_size"]
    one = gmm_flops(rows, lat, f)
    fwd_b = gmm_bytes(rows, groups, lat, f) + gmm_bytes(rows, groups, f, lat)
    bwd_b = fwd_b + tgmm_bytes(rows, groups, lat, f) + tgmm_bytes(rows, groups, f, lat)
    return {"fwd": (2 * one, fwd_b), "bwd": (4 * one, bwd_b)}


# -- the step ------------------------------------------------------------------

def _mamba_dims(c: dict) -> tuple[int, int, int]:
    """(in_proj's outputs, the inner width, the conv's channels)."""
    inner = c["mamba_num_heads"] * c["mamba_head_dim"]
    gn = c["n_groups"] * c["ssm_state_size"]
    return 2 * inner + 2 * gn + c["mamba_num_heads"], inner, inner + 2 * gn


def dense_weights(c: dict, kind: str) -> int:
    """Weights a token is multiplied with in one block of that kind, the
    grouped experts' aside."""
    d = c["hidden_size"]
    if kind == "M":
        out, inner, _ = _mamba_dims(c)
        return d * out + inner * d
    if kind == "*":
        return 2 * d * c["head_dim"] * (c["num_attention_heads"] + c["num_key_value_heads"])
    return d * (c["n_routed_experts"] + 2 * c["moe_latent_size"]
                + 2 * c["moe_shared_expert_columns_held"])


def block_params(c: dict, kind: str) -> int:
    d = c["hidden_size"]
    if kind == "M":
        _, inner, conv = _mamba_dims(c)
        small = c["conv_kernel"] * conv + conv + 3 * c["mamba_num_heads"] + inner
        return dense_weights(c, kind) + small + d
    if kind == "*":
        return dense_weights(c, kind) + d
    experts = held(c)[1] * 2 * c["moe_latent_size"] * c["moe_intermediate_size"]
    return dense_weights(c, kind) + c["n_routed_experts"] + experts + d


def params(c: dict) -> int:
    """Parameters HERE: the held share of every layer, the MTP module, the
    vocabulary's slice."""
    d = c["hidden_size"]
    blocks = sum(block_params(c, k) for k in pattern(c) + mtp_pattern(c))
    return blocks + 2 * c["vocab_size"] * d + d + 2 * d * d + 3 * d


def train_flops(cfg: dict, mix: dict) -> float:
    """Model FLOPs of forward and backward of what is here: every dense
    product 6 a weight a token (the router's, the latent projections, the
    shared expert's, W_eh); the conv 6 a tap a channel a token; the scan's
    least work forward and backward; the experts at the expected rows;
    attention's QK^T and PV over the causal pairs, 3 times forward; the head
    over the vocabulary's slice twice (the main prediction and MTP's)."""
    b, s = mix["batch"], mix["seq"]
    tokens, d = b * s, cfg["hidden_size"]
    scan = layer_ssd(cfg, b, s)
    attn = 3.0 * 4 * cfg["num_attention_heads"] * cfg["head_dim"] * flops.mean_keys(s, None) * tokens
    extra = {"M": scan["fwd"][0] + scan["bwd"][0]
             + 6.0 * cfg["conv_kernel"] * _mamba_dims(cfg)[2] * tokens,
             "*": attn, "E": 3.0 * layer_latent_gmm(cfg, b, s)["fwd"][0]}
    blocks = sum(6.0 * dense_weights(cfg, k) * tokens + extra[k]
                 for k in pattern(cfg) + mtp_pattern(cfg))
    return blocks + 6.0 * tokens * (2 * d * d + 2 * cfg["vocab_size"] * d)
