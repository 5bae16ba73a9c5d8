"""Transformer (GPT-style decoder) — TPU-first flax implementation.

The second model family of the framework (next to VGG): a causal LM built for
the parallelism layer to exercise every axis the task requires first-class:

  * dp  — batch sharding (gradient all-reduce inserted by XLA / DCN tier)
  * mdl — Megatron tensor parallelism: qkv + mlp-up column-parallel,
    out-proj + mlp-down row-parallel (`transformer_partition_rules`); XLA
    derives the all-reduces from the shardings alone.
  * sp  — sequence/context parallelism: `attn_impl="ring"` routes attention
    through `tpunet.parallel.ring_attention` (shard_map + ppermute ring,
    online softmax) so context length scales with devices.
  * ep  — expert parallelism: optional Switch-style MoE MLP whose expert
    weights carry a leading expert dim to shard over `ep`; the one-hot
    einsum dispatch lets XLA emit the all-to-alls.

Design: pre-norm blocks, RMSNorm, rotary position embeddings (global
positions — computed before the sequence dim is sharded, so ring attention
needs no position bookkeeping), no biases (TP-friendly), f32 params with
configurable compute dtype (bf16 keeps the MXU fed).

The reference repo has no model layer at all (SURVEY §2.3: TP/PP/SP/EP
"absent"); this module is capability the TPU build adds above the transport.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from tpunet.ops import attention_reference, flash_attention
from tpunet.parallel.ring_attention import ring_self_attention
from tpunet.parallel.ulysses import ulysses_self_attention


def rotary_embed(x, base: float = 10000.0, pos_offset: int = 0, positions=None):
    """Rotary position embedding. x: (b, s, h, d). pos_offset shifts to
    global positions when x is a sequence shard (cross-host ring attention —
    each process holds positions [offset, offset + s)). `positions`
    overrides with an explicit global-position vector: (s,) shared across
    the batch, or (b, s) per-row — what the per-row decode cache needs,
    where each batch slot sits at its own sequence offset."""
    _, s, _, d = x.shape
    half = d // 2
    freqs = jnp.exp(-math.log(base) * jnp.arange(0, half, dtype=jnp.float32) / half)
    if positions is None:
        positions = pos_offset + jnp.arange(s, dtype=jnp.float32)
    angles = positions.astype(jnp.float32)[..., :, None] * freqs  # (…, s, half)
    if angles.ndim == 2:
        angles = angles[None]  # shared positions -> one broadcast batch row
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    rot = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return rot.astype(x.dtype)


class RMSNorm(nn.Module):
    """unit_offset: the learned scale is stored as its offset from 1 (zeros
    at initialisation) and applied as (1 + scale). out_dtype: the output's
    type; None = the input's. The model's norms give compute_dtype, which a
    float32 residual stream (`residual_dtype`) makes another type than x's."""

    eps: float = 1e-6
    unit_offset: bool = False
    out_dtype: jnp.dtype | None = None

    @nn.compact
    def __call__(self, x):
        init = nn.initializers.zeros if self.unit_offset else nn.initializers.ones
        scale = self.param("scale", init, (x.shape[-1],))
        if self.unit_offset:
            scale = 1.0 + scale
        x32 = x.astype(jnp.float32)
        norm = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps)
        return (norm * scale).astype(self.out_dtype or x.dtype)


class QuantDense(nn.Module):
    """Weight-only int8 Dense: kernel stored int8 with a per-output-channel
    f32 scale (w ≈ q · scale, symmetric absmax). Decode is weight-HBM-
    bandwidth-bound, so halving the kernel bytes is a direct tokens/s
    lever; the dequant is a cast + column scale that XLA fuses around the
    dot, so the int8 tensor is what actually streams from HBM. Params come
    from `tpunet.models.quantize_params` on a trained fp tree — a fresh
    init is a zero skeleton (shape/dtype template only). Inference path;
    int8 params take no gradients."""

    features: int
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        q = self.param("q", nn.initializers.zeros,
                       (x.shape[-1], self.features), jnp.int8)
        scale = self.param("scale", nn.initializers.ones,
                           (self.features,), jnp.float32)
        y = x.astype(self.dtype) @ q.astype(self.dtype)
        return y * scale.astype(self.dtype)


class LoraDense(nn.Module):
    """Dense with a rank-r LoRA adapter: y = base(x) + (x @ A) @ B ·
    (alpha/r). B initializes to ZERO, so a freshly-adapted model is
    bitwise the base model; training typically updates only A/B
    (`tpunet.models.lora_optimizer` — NOT bare optax.masked, which passes
    raw gradients through to the "frozen" base) — the base stays frozen,
    which is the parameter-efficient point. `quant=True` puts the base in int8
    (QLoRA-style: frozen quantized weights stream at half bandwidth,
    trainable adapters stay fp). Base params live under the "base"
    submodule with their ordinary leaf names (kernel, or q/scale);
    `tpunet.models.lora.graft_base` maps a base checkpoint /
    quantize_params output into the adapted tree."""

    features: int
    rank: int
    dtype: jnp.dtype = jnp.bfloat16
    alpha: float | None = None  # None -> rank (scale 1)
    quant: bool = False

    @nn.compact
    def __call__(self, x):
        base = (QuantDense(self.features, dtype=self.dtype, name="base")
                if self.quant else
                nn.Dense(self.features, use_bias=False, dtype=self.dtype,
                         name="base"))
        y = base(x)
        a = self.param("lora_a", nn.initializers.normal(0.02),
                       (x.shape[-1], self.rank), jnp.float32)
        bmat = self.param("lora_b", nn.initializers.zeros,
                          (self.rank, self.features), jnp.float32)
        scale = (self.alpha if self.alpha is not None else self.rank
                 ) / self.rank
        delta = (x.astype(self.dtype) @ a.astype(self.dtype)
                 ) @ bmat.astype(self.dtype)
        return y + delta * jnp.asarray(scale, self.dtype)


def _dense(features, dtype, name, weight_quant, lora_rank=0, lora_alpha=None):
    """The Dense factory every matmul in this family goes through: fp by
    default, QuantDense under weight_quant="int8" — SAME module names, so
    the quantized param tree is the fp tree with each kernel dict swapped
    for {q, scale} (what quantize_params produces) — and LoraDense when
    lora_rank > 0 (base params nested under "base", adapters alongside)."""
    if lora_rank > 0:
        return LoraDense(features, lora_rank, dtype=dtype, alpha=lora_alpha,
                         quant=weight_quant is not None, name=name)
    if weight_quant is None:
        return nn.Dense(features, use_bias=False, dtype=dtype, name=name)
    return QuantDense(features, dtype=dtype, name=name)


def _causal_kernel_attention(q, k, v, attn_impl, window):
    """The flash/reference causal-attention pair on rotary'd (b, s, heads,
    dh) tensors — ONE dispatch shared by the ordinary forward and the
    kernel-routed prefill, so window handling and the GQA convention can't
    diverge between them: flash consumes kv-head tensors natively; the
    reference einsum gets a (fused) group repeat, a no-op when k/v already
    carry full heads."""
    if attn_impl == "flash":
        return flash_attention(q, k, v, True, window=window)
    from tpunet.ops.flash_attention import _repeat_kv

    group = q.shape[2] // k.shape[2]
    return attention_reference(q, _repeat_kv(k, group), _repeat_kv(v, group),
                               True, window=window)


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    """What ONE block is built from: the one field of `Block` and of
    `SelfAttention`, each of which reads `self.spec.<name>` where it uses
    it. `Transformer.layer_specs()` is the only code that makes one, and it
    fills every field but `head_dim` (the model's, or d_model / n_heads),
    `rotary` (by the layer's place in `attn_pattern`) and `kind` (by its
    place in `layer_pattern` or `mtp_pattern`) from the model's field of the
    same name — so a new block-level field is a declaration
    here, one on `Transformer` (which holds the default and the comment
    users read) and its use. No defaults here: a spec never exists apart
    from a model."""

    n_heads: int
    head_dim: int
    d_ff: int
    n_experts: int
    capacity_factor: float
    moe_top_k: int
    compute_dtype: jnp.dtype
    attn_impl: str
    mesh: Mesh | None
    dp_axis: str | None
    sp_axis: str
    tp_axis: str | None
    n_kv_heads: int | None
    mlp_impl: str
    decode: bool
    attn_window: int | None
    weight_quant: str | None
    prefill: bool
    per_row_cache: bool
    decode_ring_cache: bool
    lora_rank: int
    lora_alpha: float | None
    norm_eps: float
    norm_unit_offset: bool
    rope_theta: float
    eva_window: int | None
    eva_chunk: int | None
    rotary: bool
    moe_impl: str
    moe_held: tuple[int, int] | None
    moe_activation: str
    moe_router_input: str
    qk_norm: bool
    attn_select_top_k: int | None
    attn_index_heads: int
    attn_index_head_dim: int
    kind: str | None
    mamba_heads: int
    mamba_head_dim: int
    mamba_groups: int
    mamba_state: int
    mamba_conv: int
    mamba_chunk: int
    moe_scoring: str
    moe_routed_scale: float
    moe_latent: int
    moe_shared_d_ff: int


class SelfAttention(nn.Module):
    """Causal multi-head self-attention with pluggable impl.

    attn_impl: "reference" (einsum softmax), "flash" (Pallas kernel),
    "zigzag" (balanced causal CP; feed tokens through to_zigzag),
    "ring" / "ulysses" (sequence-parallel attention over `sp_axis` of
    `mesh` — k/v ring rotation vs all-to-all head re-sharding), or
    "dcn_ring" / "dcn_ulysses" / "dcn_zigzag" (sequence sharded across
    PROCESSES over the tpunet DCN transport — requires
    tpunet.distributed.initialize(); dcn_zigzag additionally expects each
    process's shard to be its zigzag chunk pair, i.e. tokens fed through
    to_zigzag, and is the balanced-causal variant of dcn_ring), or "eva"
    (tpunet.ops.eva_attention: exact causal attention inside each
    `eva_window`, one learned summary per `eva_chunk` positions of every
    earlier window, one softmax over both; the per-head vectors
    `adaptive_phi` and `adaptive_mu_k` are this module's parameters).

    n_kv_heads < n_heads is grouped-query attention: k/v are projected to
    n_kv_heads — the kv projection params/FLOPs and (in decode) the KV
    cache shrink by n_heads/n_kv_heads. The flash impl consumes the
    kv-head tensors natively (in-kernel GQA: K/V stream at 1/group
    bandwidth); every other impl receives a post-rotary broadcast to
    ordinary MHA shapes.

    attn_select_top_k = k makes the DATA decide which keys a query sees
    (tpunet.ops.dsa_attention): an indexer of `attn_index_heads` heads of
    `attn_index_head_dim` over one key head scores every earlier key for
    every query from the DETACHED input, the k best are kept (all of them
    while a query has no more than k), and the softmax runs over those
    alone. The selection passes no gradient; the indexer learns from a loss
    of its own, the KL divergence from the main attention's probabilities
    (mean over heads, detached) to the softmax of its scores over the
    selection, sown under `intermediates/dsa_index_loss` for the trainer to
    add, beside `dsa_selected_pairs`. attn_impl "flash" runs the kernels,
    "reference" the plain forms. qk_norm: an RMSNorm a head, of its own
    scale, on q and on k before the rotary.

    decode=True switches to autoregressive inference: a "cache" collection
    holds cached_key/cached_value buffers sized by the INIT input's
    sequence length (init with a max-length dummy), and each apply consumes
    the next s tokens (usually 1), attending over the filled prefix.

    attn_window + decode + decode_ring_cache (the default) makes the cache
    a TRUE rolling ring buffer (Mistral-style): leaves are sized
    min(window, capacity), writes land at position mod window, and each
    decode step contracts over window (+ s) entries instead of the full
    capacity — sliding-window attention as a *serving* feature (bounded
    memory, O(window) decode compute), not just a masking pattern.
    """

    spec: LayerSpec

    @nn.compact
    def __call__(self, x):
        spec = self.spec
        b, s, _ = x.shape
        h, dh = spec.n_heads, spec.head_dim
        kv = spec.n_kv_heads or h
        if h % kv:
            raise ValueError(f"n_heads {h} not divisible by n_kv_heads {kv}")
        if spec.attn_impl == "eva" and (
                kv != h or not spec.eva_window or not spec.eva_chunk):
            raise ValueError("attn_impl='eva' needs eva_window, eva_chunk and "
                             "n_kv_heads == n_heads")
        if spec.attn_window is not None and spec.attn_impl not in (
            "reference", "flash"
        ):
            raise ValueError(
                f"attn_window is only supported by attn_impl 'reference'/"
                f"'flash', not {spec.attn_impl!r}"
            )
        select = spec.attn_select_top_k is not None
        if select and spec.decode:
            raise ValueError(
                "decode=True with attn_select_top_k is not supported yet: a "
                "step would have to score the cached keys with the indexer, "
                "which needs a cache of the indexer's keys beside the KV "
                "cache and a selection inside the cached attention (ROADMAP "
                "Reach A11); score the full sequence with decode=False")
        if select and (spec.attn_impl not in ("reference", "flash")
                       or spec.attn_window is not None
                       or spec.attn_index_heads < 1 or spec.attn_index_head_dim < 1):
            raise ValueError(
                "attn_select_top_k needs attn_impl 'reference' or 'flash', no "
                "attn_window, and the indexer's attn_index_heads and "
                "attn_index_head_dim")
        dt = spec.compute_dtype
        proj = lambda nh, name: _dense(nh * dh, dt, name, spec.weight_quant, spec.lora_rank, spec.lora_alpha)
        q = proj(h, "q")(x).reshape(b, s, h, dh)
        k = proj(kv, "k")(x).reshape(b, s, kv, dh)
        v = proj(kv, "v")(x).reshape(b, s, kv, dh)
        if spec.qk_norm:
            q = RMSNorm(spec.norm_eps, spec.norm_unit_offset, dt, name="q_norm")(q)
            k = RMSNorm(spec.norm_eps, spec.norm_unit_offset, dt, name="k_norm")(k)

        if spec.decode:
            # The cached step below is dense local attention — correct for
            # "reference"/"flash" (same math), semantically WRONG for the
            # sequence-parallel impls (sharded/permuted inputs, cross-device
            # k/v). Fail loud rather than generate silent garbage.
            if spec.attn_impl not in ("reference", "flash"):
                raise ValueError(
                    f"decode=True does not support attn_impl="
                    f"{spec.attn_impl!r}; decode on the full sequence with "
                    "attn_impl='reference' (e.g. model.clone("
                    "attn_impl='reference') before generate())"
                )
            # flax decode-cache pattern: the variables are CREATED on the
            # init call (whose input sets the cache capacity = its seq len)
            # which otherwise runs the ordinary causal path below; every
            # later apply with mutable=["cache"] takes the step branch.
            ring = spec.attn_window is not None and spec.decode_ring_cache
            # Ring mode sizes the leaves at min(window, capacity) — the
            # init call's s IS the capacity (init with a max-length dummy),
            # so eval_shape-based init_cache allocates O(window) for free.
            cshape = ((b, min(spec.attn_window, s), kv, dh) if ring
                      else k.shape)
            filled = self.has_variable("cache", "cached_key")
            ckey = self.variable("cache", "cached_key", jnp.zeros, cshape, k.dtype)
            cval = self.variable("cache", "cached_value", jnp.zeros, cshape, v.dtype)
            cidx = self.variable(
                "cache", "cache_index",
                lambda: jnp.zeros((b,) if spec.per_row_cache else (),
                                  jnp.int32)
            )
            if filled:
                idx = cidx.value
                cap = ckey.value.shape[1]
                if spec.rotary:
                    step_pos = (idx[..., None] + jnp.arange(s)).astype(jnp.float32)
                    q = rotary_embed(q, spec.rope_theta, positions=step_pos)
                    k = rotary_embed(k, spec.rope_theta, positions=step_pos)
                rows = jnp.arange(b)[:, None]
                if ring:
                    # A full-width ring never overflows: writes land at pos
                    # mod cap and the window mask only addresses the last
                    # `window` positions, all resident. But when the cache
                    # was allocated SMALLER than the window (cap < window),
                    # the ring wraps before the window does — eviction would
                    # silently corrupt in-window history, so keep the loud
                    # NaN-poison past capacity. Both sizes are static.
                    if cap < spec.attn_window:
                        overflow = idx + s > cap
                    else:
                        overflow = jnp.zeros(idx.shape, bool)
                    # Attention reads the PRE-write ring (positions < idx)
                    # plus the in-step k/v — exact for s > 1 too, where a
                    # post-write ring would have overwritten entries the
                    # step's earlier queries still see.
                    ring_k, ring_v = ckey.value, cval.value
                    m = min(s, cap)  # static: a step writes its last m
                    wpos = idx[..., None] + jnp.arange(s - m, s)
                    slot = jnp.mod(wpos, cap)  # (m,) or (b, m), all distinct
                    if spec.per_row_cache:
                        ckey.value = ckey.value.at[rows, slot].set(k[:, s - m:])
                        cval.value = cval.value.at[rows, slot].set(v[:, s - m:])
                    else:
                        ckey.value = ckey.value.at[:, slot].set(k[:, s - m:])
                        cval.value = cval.value.at[:, slot].set(v[:, s - m:])
                else:
                    # Past-capacity steps would clamp the write start and
                    # silently corrupt the tail; idx is traced, so the
                    # jit-compatible hard failure is poisoning the output to
                    # NaN the moment idx + s overflows — loud at the first
                    # sample. Per-row mode: everything here is (b,)-shaped —
                    # each batch slot sits at its own sequence offset
                    # (continuous batching), overflow poisons only its own
                    # row, and the cache write is a per-row scatter instead
                    # of one slice.
                    overflow = idx + s > cap
                    if spec.per_row_cache:
                        pos_i = idx[:, None] + jnp.arange(s)  # (b, s)
                        ckey.value = ckey.value.at[rows, pos_i].set(k)
                        cval.value = cval.value.at[rows, pos_i].set(v)
                    else:
                        ckey.value = jax.lax.dynamic_update_slice(
                            ckey.value, k, (0, idx, 0, 0)
                        )
                        cval.value = jax.lax.dynamic_update_slice(
                            cval.value, v, (0, idx, 0, 0)
                        )
                cidx.value = idx + s
                if spec.prefill:
                    # First fill of an EMPTY cache: the block attends only
                    # within itself, which is plain causal self-attention —
                    # run it through the configured kernel (flash: O(s)
                    # memory, MXU-tiled; untileable prompt lengths fall
                    # back to the reference einsum over s x s, still
                    # smaller than the s x cap masked dense below). The
                    # cache write above is all decode needs later. Only
                    # valid at idx == 0 — poisoned to NaN otherwise, same
                    # discipline as the overflow guard.
                    o = _causal_kernel_attention(
                        q, k, v, spec.attn_impl, spec.attn_window)
                    bad = overflow | (idx != 0)
                    if spec.per_row_cache:
                        bad = bad[:, None, None, None]  # poison own row only
                    o = jnp.where(bad, jnp.nan, o).astype(dt)
                    o = o.reshape(b, s, h * dh)
                    return _dense(x.shape[-1], dt, "out", spec.weight_quant,
                                  spec.lora_rank, spec.lora_alpha)(o)
                # Grouped einsum: q reshaped to (b, s, kv, group, dh)
                # contracts DIRECTLY against the (b, K, kv, dh) cache —
                # the group-repeated K/V never exists in HBM. This is the
                # point of GQA at decode time: the cache read per step is
                # kv/h of the MHA equivalent, and materializing a repeat
                # would hand that bandwidth win straight back.
                if ring:
                    # Contract over [pre-write ring | in-step k/v]:
                    # K = window + s entries, not the full capacity. Ring
                    # slot j's global position is the largest p < idx with
                    # p ≡ j (mod cap); p < 0 means never written (or the
                    # previous occupant of a recycled serve slot — idx was
                    # reset, so stale entries are unaddressable by
                    # construction).
                    att_k = jnp.concatenate([ring_k, k], axis=1)
                    att_v = jnp.concatenate([ring_v, v], axis=1)
                    j = jnp.arange(cap)
                    p_ring = (idx[..., None] - 1
                              - jnp.mod(idx[..., None] - 1 - j, cap))
                    p_step = idx[..., None] + jnp.arange(s)
                    key_pos = jnp.concatenate(
                        [jnp.broadcast_to(p_ring, idx.shape + (cap,)),
                         jnp.broadcast_to(p_step, idx.shape + (s,))],
                        axis=-1)  # (K,) or (b, K)
                else:
                    att_k, att_v = ckey.value, cval.value
                    key_pos = jnp.arange(cap)
                qg = q.reshape(b, s, kv, h // kv, dh).astype(jnp.float32)
                # (b, kv, group, s, K) scores; mask to keys at valid global
                # positions <= each query's position (and in-window).
                scores = jnp.einsum(
                    "bqhgd,bkhd->bhgqk", qg, att_k.astype(jnp.float32)
                ) / math.sqrt(dh)
                kp = (key_pos[:, None, None, None, :] if key_pos.ndim == 2
                      else key_pos[None, None, None, None, :])
                pos = idx[..., None] + jnp.arange(s)  # (s,) or (b, s)
                if spec.per_row_cache:
                    q_pos = pos[:, None, None, :, None]
                    row_overflow = overflow[:, None, None, None]
                else:
                    q_pos = pos[None, None, None, :, None]
                    row_overflow = overflow
                keep = (kp >= 0) & (kp <= q_pos)
                if spec.attn_window is not None:
                    keep &= (q_pos - kp) < spec.attn_window
                scores = jnp.where(keep, scores, -jnp.inf)
                probs = jax.nn.softmax(scores, axis=-1)
                o = jnp.einsum(
                    "bhgqk,bkhd->bqhgd", probs, att_v.astype(jnp.float32)
                ).reshape(b, s, h, dh)
                o = jnp.where(row_overflow, jnp.nan, o)
                o = o.astype(dt).reshape(b, s, h * dh)
                return _dense(x.shape[-1], dt, "out", spec.weight_quant,
                              spec.lora_rank, spec.lora_alpha)(o)

        pos_offset = 0
        positions = None
        if spec.attn_impl in ("dcn_ring", "dcn_ulysses"):
            # The per-process model sees only its sequence shard; rotary
            # must use global positions for the ring to be coherent.
            from tpunet import distributed

            pos_offset = distributed.rank() * s
        elif spec.attn_impl == "dcn_zigzag":
            # Per-process shard = zigzag chunk pair of the global sequence.
            from tpunet import distributed
            from tpunet.parallel.zigzag_attention import zigzag_positions

            positions = zigzag_positions(
                distributed.world_size(),
                distributed.world_size() * s,
                distributed.rank(),
            ).astype(jnp.float32)
        elif spec.attn_impl == "zigzag":
            # The WHOLE sequence axis is in zigzag chunk order (tokens fed
            # through to_zigzag); rotary needs each row's natural position.
            from tpunet.parallel.zigzag_attention import to_zigzag

            if spec.mesh is None:
                raise ValueError("attn_impl='zigzag' requires a mesh")
            positions = to_zigzag(
                jnp.arange(s, dtype=jnp.float32),
                spec.mesh.shape[spec.sp_axis], axis=0,
            )
        if spec.rotary:
            q = rotary_embed(q, spec.rope_theta, pos_offset, positions)
            k = rotary_embed(k, spec.rope_theta, pos_offset, positions)
        if kv != h and spec.attn_impl != "flash":
            # GQA broadcast AFTER rotary (rotary runs on the kv heads): the
            # projection savings are already banked; every impl below then
            # sees plain MHA shapes. XLA fuses the repeat into the consumer.
            # The flash kernel is EXCLUDED: it consumes kv-head tensors
            # natively (per-head BlockSpec index_map), streaming K/V at
            # 1/group the HBM bandwidth instead of reading a repeat.
            k = jnp.repeat(k, h // kv, axis=2)
            v = jnp.repeat(v, h // kv, axis=2)

        if select:
            o = self._selected(x, q, k, v)
        elif spec.attn_impl == "eva":
            from tpunet.ops.eva_attention import eva_attention

            def vec(key, shape):  # the released model's initialisation
                return jnp.clip(jax.random.normal(key, shape), -1.0, 1.0) / math.sqrt(dh)

            o = eva_attention(q, k, v, self.param("adaptive_phi", vec, (h, dh)),
                              self.param("adaptive_mu_k", vec, (h, dh)),
                              spec.eva_window, spec.eva_chunk)
        elif spec.attn_impl == "zigzag":
            from tpunet.parallel.zigzag_attention import zigzag_self_attention

            o = zigzag_self_attention(
                q, k, v, spec.mesh,
                dp_axis=spec.dp_axis, sp_axis=spec.sp_axis, tp_axis=spec.tp_axis,
            )
        elif spec.attn_impl in ("ring", "ulysses"):
            if spec.mesh is None:
                raise ValueError(f"attn_impl={spec.attn_impl!r} requires a mesh")
            sp_fn = ring_self_attention if spec.attn_impl == "ring" else ulysses_self_attention
            o = sp_fn(
                q, k, v, spec.mesh, causal=True,
                dp_axis=spec.dp_axis, sp_axis=spec.sp_axis, tp_axis=spec.tp_axis,
            )
        elif spec.attn_impl == "dcn_ring":
            from tpunet.parallel.dcn_ring_attention import dcn_ring_attention

            o = dcn_ring_attention(q, k, v, causal=True)
        elif spec.attn_impl == "dcn_zigzag":
            from tpunet.parallel.dcn_ring_attention import dcn_zigzag_attention

            o = dcn_zigzag_attention(q, k, v)
        elif spec.attn_impl == "dcn_ulysses":
            from tpunet.parallel.ulysses import dcn_ulysses_attention

            o = dcn_ulysses_attention(q, k, v, causal=True)
        else:  # flash / reference — k/v are pre-broadcast for non-flash
            o = _causal_kernel_attention(
                q, k, v, spec.attn_impl, spec.attn_window)

        o = o.reshape(b, s, h * dh)
        return _dense(x.shape[-1], dt, "out", spec.weight_quant,
                      spec.lora_rank, spec.lora_alpha)(o)

    @nn.nowrap
    def _selected(self, x, q, k, v):
        """Attention over the keys the indexer selects. x: the block's
        normalised input; q: (b, s, h, dh), k, v: (b, s, kv, dh), after norm
        and rotary, k and v at their own (grouped) width."""
        from tpunet.ops import dsa_attention as dsa

        spec = self.spec
        b, s, _ = x.shape
        hi, di, dt = spec.attn_index_heads, spec.attn_index_head_dim, spec.compute_dtype
        kernels = spec.attn_impl == "flash"
        dense = lambda n, name: nn.Dense(n, use_bias=False, dtype=dt, name=name)  # noqa: E731
        with jax.named_scope("dsa.index"):
            # the indexer reads the input DETACHED: its loss moves its own
            # weights and nothing before them
            xi = jax.lax.stop_gradient(x)
            qi = dense(hi * di, "index_q")(xi).reshape(b, s, hi, di)
            ki = nn.LayerNorm(epsilon=1e-6, dtype=dt, name="index_k_norm")(
                dense(di, "index_k")(xi)).reshape(b, s, 1, di)
            if spec.rotary:
                qi = rotary_embed(qi, spec.rope_theta)
                ki = rotary_embed(ki, spec.rope_theta)
            ki = ki[:, :, 0]
            w = dense(hi, "index_w")(xi).astype(jnp.float32) * (hi * di) ** -0.5
            scores = jax.lax.stop_gradient(
                dsa.index_scores(qi, ki, w) if kernels
                else dsa.index_scores_reference(qi, ki, w))
        with jax.named_scope("dsa.select"):
            mask, pairs = dsa.select(scores, spec.attn_select_top_k, kernel=kernels)
        self.sow("intermediates", "dsa_selected_pairs", pairs)
        if kernels:
            o, lse = dsa.selected_attention(q, k, v, mask, with_lse=True)
        else:
            o, lse = dsa.selected_attention_reference(q, k, v, mask), None
        with jax.named_scope("dsa.kl"):
            self.sow("intermediates", "dsa_index_loss",
                     dsa.index_loss(q, k, mask, scores, qi, ki, w, lse=lse))
        return o


class Mlp(nn.Module):
    """Dense MLP: "gelu" (up→gelu→down) or "swiglu" (silu(gate)·up→down,
    the LLaMA-family FFN). Both keep every kernel bias-free and 2-D so the
    Megatron TP rules (up/gate column-parallel, down row-parallel) apply."""

    d_ff: int
    compute_dtype: jnp.dtype = jnp.bfloat16
    mlp_impl: str = "gelu"
    weight_quant: str | None = None
    lora_rank: int = 0
    lora_alpha: float | None = None

    @nn.compact
    def __call__(self, x):
        dt = self.compute_dtype
        wq, lr, la = self.weight_quant, self.lora_rank, self.lora_alpha
        if self.mlp_impl == "swiglu":
            g = _dense(self.d_ff, dt, "gate", wq, lr, la)(x)
            h = _dense(self.d_ff, dt, "up", wq, lr, la)(x)
            h = nn.silu(g) * h
        elif self.mlp_impl == "gelu":
            h = _dense(self.d_ff, dt, "up", wq, lr, la)(x)
            h = nn.gelu(h)
        else:
            raise ValueError(f"unknown mlp_impl {self.mlp_impl!r}")
        return _dense(x.shape[-1], dt, "down", wq, lr, la)(h)


class MoeMlp(nn.Module):
    """Top-k MoE with capacity-bounded one-hot einsum dispatch (top_k=1 is
    Switch routing — the default; top_k=2 is the GShard/Mixtral family).

    Expert weights carry a leading expert dim — shard it over the `ep` mesh
    axis (`transformer_partition_rules`) and XLA turns the dispatch/combine
    einsums into all-to-alls. Tokens over capacity are dropped (residual
    passes them through unchanged), the standard Switch behavior; capacity
    scales with top_k (cap = ceil(k·t/e · capacity_factor)) and slots are
    granted choice-major, so a token's SECONDARY expert overflowing can
    never evict another token's primary assignment. Combine weights are the
    chosen probs (top_k=1, Switch) or the probs renormalized over the
    chosen set (top_k>1, Mixtral convention). The router load-balancing
    loss — primary-assignment fractions, reducing to the Switch formula at
    k=1 — is sown under `intermediates/moe_aux_loss`.
    """

    n_experts: int
    d_ff: int
    capacity_factor: float = 1.25
    compute_dtype: jnp.dtype = jnp.bfloat16
    top_k: int = 1

    @nn.compact
    def __call__(self, x):
        b, s, d = x.shape
        e, f, dt = self.n_experts, self.d_ff, self.compute_dtype
        k = self.top_k
        if not 1 <= k <= e:
            raise ValueError(f"top_k {k} outside [1, n_experts={e}]")
        t = b * s
        cap = max(1, int(math.ceil(k * t / e * self.capacity_factor)))

        wg = self.param("router", nn.initializers.lecun_normal(), (d, e))
        wi = self.param("wi", nn.initializers.lecun_normal(), (e, d, f))
        wo = self.param("wo", nn.initializers.lecun_normal(), (e, f, d))

        xt = x.reshape(t, d)
        logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), wg.astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)
        gates, experts = jax.lax.top_k(probs, k)  # (t, k) each, best first
        if k > 1:
            gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
        onehot = jax.nn.one_hot(experts, e, dtype=jnp.float32)  # (t, k, e)

        # Load-balancing aux loss over the PRIMARY assignment:
        # e * sum_e(frac_tokens * frac_prob) — the Switch formula at k=1.
        frac_tokens = jnp.mean(onehot[:, 0, :], axis=0)
        frac_probs = jnp.mean(probs, axis=0)
        self.sow("intermediates", "moe_aux_loss", e * jnp.sum(frac_tokens * frac_probs))

        # Position of each (token, choice) within its expert's capacity
        # buffer. The cumsum runs CHOICE-MAJOR (all choice-0 rows before any
        # choice-1 row): primary assignments claim slots first.
        oh_flat = onehot.transpose(1, 0, 2).reshape(k * t, e)
        pos = jnp.cumsum(oh_flat, axis=0) * oh_flat        # 1-based
        keep = (pos > 0) & (pos <= cap)
        slot = jnp.clip(pos - 1, 0, cap - 1).astype(jnp.int32)
        slot_oh = jax.nn.one_hot(
            jnp.sum(slot * oh_flat.astype(jnp.int32), axis=-1), cap,
            dtype=jnp.float32)
        dispatch = ((oh_flat * keep)[:, :, None] * slot_oh[:, None, :]
                    ).reshape(k, t, e, cap).transpose(1, 0, 2, 3)  # (t,k,e,c)

        xe = jnp.einsum("tkec,td->ecd", dispatch.astype(dt), xt.astype(dt))
        hdn = nn.gelu(jnp.einsum("ecd,edf->ecf", xe, wi.astype(dt)))
        ye = jnp.einsum("ecf,efd->ecd", hdn, wo.astype(dt))
        # Combine weighted by each choice's gate; dropped (over-capacity)
        # choices contribute nothing, matching the dispatch side.
        combine = dispatch * gates[:, :, None, None].astype(dispatch.dtype)
        yt = jnp.einsum("tkec,ecd->td", combine.astype(dt), ye)
        return yt.reshape(b, s, d)


_SPARE_ROWS = 512  # zero rows appended for the padding rows to read


def _gather_rows(src, plan):
    """(tokens, d) -> (rows, d): buffer row r takes its token's row, a
    padding row one of the zero rows appended for them (one gather, no mask
    after; MANY zero rows, because three rows in four are padding and a
    gather whose indices all name one row is served by one memory channel)."""
    rows_token = plan[0]
    spare = jnp.zeros((_SPARE_ROWS, src.shape[1]), src.dtype)
    return jnp.concatenate([src, spare])[rows_token]


def _sum_slots(buf, weight, place):
    """(rows, d) -> (tokens, d): token t takes sum_j weight[t, j] *
    buf[place[t, j]] over its slots j, accumulated in float32 a slot at a
    time (one gather of (tokens, d) a slot, never a (tokens, slots, d)
    copy)."""
    out = jnp.zeros((place.shape[0], buf.shape[1]), jnp.float32)
    for j in range(place.shape[1]):
        out = out + weight[:, j, None] * buf[place[:, j]].astype(jnp.float32)
    return out


# The two permutations of an expert layer, each the other's transpose, so
# that forward AND backward are gathers: a row scatter-add, which is what
# autodiff makes of a gather, serialises on a TPU. A token's sums run over
# m = min(k, count) SLOTS: its k choices where k <= count, else the `count`
# held experts (a token's choices are distinct experts, so it has at most
# one pair in each). `plan` = (rows_token, rows_pair, place, here): per
# buffer row its token (for a padding row one of `_SPARE_ROWS` indices past
# the tokens) and the (token, slot) pair that sits in it (as token * m +
# slot; for a padding row any pair); per slot its row (for a slot that
# holds nothing its own token's row: its weight is 0) and whether it holds
# a pair. The indices that stand for nothing are SPREAD, never one index
# many times: see `_gather_rows`.

@jax.custom_vjp
def _dispatch(u, plan):
    return _gather_rows(u, plan)


def _dispatch_fwd(u, plan):
    return _dispatch(u, plan), plan


def _dispatch_bwd(plan, dxs):
    _, _, place, here = plan
    return _sum_slots(dxs, here.astype(jnp.float32), place).astype(dxs.dtype), None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(y, gates, plan):
    _, _, place, here = plan
    return _sum_slots(y, jnp.where(here, gates, 0.0), place).astype(y.dtype)


def _combine_fwd(y, gates, plan):
    return _combine(y, gates, plan), (y, gates, plan)


def _combine_bwd(res, dout):
    y, gates, plan = res
    _, rows_pair, place, here = plan
    dy = (_gather_rows(dout, plan)
          * gates.reshape(-1)[rows_pair][:, None].astype(dout.dtype))
    dgates = jnp.stack(
        [jnp.sum(dout.astype(jnp.float32) * y[place[:, j]].astype(jnp.float32), axis=-1)
         for j in range(place.shape[1])], axis=1)
    return dy, jnp.where(here, dgates, 0.0).astype(gates.dtype), None


_combine.defvjp(_combine_fwd, _combine_bwd)


class GroupedExperts(nn.Module):
    """Top-k mixture of gated experts that drops nothing, and that is told
    which experts it holds.

    The router is `n_experts` wide and reads `h`, which `Block` hands over:
    the input of the block's ATTENTION (so the choice is known before
    attention has run; `moe_router_input="attn_input"`) or the expert
    layer's own input `u` ("mlp_input"); every
    token takes its `top_k` best logits and weighs them by a softmax over
    those k. Of the experts this module holds `held` = (first, count),
    default all: it routes over all `n_experts`, computes its own experts'
    part of the result, down(act(gate u) * (up u)) of the block's second
    norm `u` with `activation` "relu" (ReGLU) or "silu" (SwiGLU), and adds
    NOTHING for the others — what the chips that hold
    them would add is theirs to send (expert parallelism; on one chip the
    layer runs without an exchange, and the partial result is the layer's
    output). With all held it is the whole layer.

    Dispatch is sorted and grouped, not one-hot: the (token, choice) pairs
    that fall on held experts are ranked inside their expert, placed into a
    row buffer in which every expert starts on a tile boundary
    (tpunet.ops.grouped_matmul), multiplied by three grouped products,
    scaled by their gate and summed back into their token. The buffer is
    sized for the worst case a static shape must allow (tokens x min(top_k,
    count) rows and a tile an expert), so NO token is dropped whatever the
    imbalance; tiles past the true count cost no product. Nothing is sown
    under `moe_aux_loss`: the loss is the cross-entropy alone. Sown under
    `intermediates`: `moe_rows_held`, the (token, choice) pairs that fell on
    held experts, and `moe_rows_max`, the fullest held expert's. A token's
    sum runs over min(top_k, count) slots: its choices, or where count <
    top_k its held experts, and then `moe_choices_held_max` is sown too, the
    most held experts any token chose.

    Weights: router (d, n_experts); gate, up (count, d, d_ff); down (count,
    d_ff, d); float32 masters, compute_dtype into the products. The router's
    product is float32 at Precision.HIGHEST: under the default an f32 x f32
    product is ONE bf16 pass on a TPU, and its rounding decides which
    experts a token gets.

    The latent form (Nemotron-H's LatentMoE) is four fields, each off by
    default. `scoring="sigmoid"`: a token's scores are sigmoid(logits), it
    takes the top_k of scores + `router_bias` (an (n_experts,) leaf that
    takes no gradient: zero at initialisation, it is what an auxiliary-
    loss-free balancer moves, and nothing here moves it) and weighs them by
    score / (sum of the chosen scores). `routed_scale` multiplies every
    gate. `latent` > 0: the experts work in that width, between
    `to_latent` (d, latent) and `from_latent` (latent, d), applied once a
    token around the sum over its experts. `activation="relu2"`: experts
    of TWO matrices, down(relu(up u)^2). `shared_d_ff` > 0: a shared
    expert every token takes at weight 1, in the hidden width, of the
    experts' form (`shared_gate`, `shared_up`, `shared_down`); its columns
    are separable, so a tensor-parallel share of them is the same module
    with fewer columns."""

    n_experts: int
    top_k: int
    d_ff: int
    held: tuple[int, int] | None = None
    compute_dtype: jnp.dtype = jnp.bfloat16
    activation: str = "relu"
    scoring: str = "softmax"
    routed_scale: float = 1.0
    latent: int = 0
    shared_d_ff: int = 0

    @nn.compact
    def __call__(self, u, h):
        from tpunet.ops.grouped_matmul import (buffer_rows, group_tiles,
                                               grouped_matmul, tile_rows)

        b, s, d = u.shape
        e, k, f, dt = self.n_experts, self.top_k, self.d_ff, self.compute_dtype
        first, count = self.held or (0, e)
        if not 1 <= k <= e or first < 0 or count < 1 or first + count > e:
            raise ValueError(f"top_k {k}, held {(first, count)} outside "
                             f"n_experts={e}")
        if self.activation not in ("relu", "silu", "relu2"):
            raise ValueError(f"unknown moe_activation {self.activation!r}")
        if self.scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"unknown moe_scoring {self.scoring!r}")
        expert = _expert_fn(self.activation)
        t = b * s
        width = self.latent or d
        init = nn.initializers.normal(0.02)
        router = self.param("router", init, (d, e))
        if self.scoring == "sigmoid":
            bias = jax.lax.stop_gradient(
                self.param("router_bias", nn.initializers.zeros, (e,)))
        mats = [self.param(name, init, shape) for name, shape in _expert_shapes(
            self.activation, (count, width, f), (count, f, width))]

        with jax.named_scope("moe.route"):
            logits = jnp.dot(h.reshape(t, d).astype(jnp.float32),
                             router.astype(jnp.float32),
                             precision=jax.lax.Precision.HIGHEST)
            if self.scoring == "sigmoid":
                scores = jax.nn.sigmoid(logits)
                _, experts = jax.lax.top_k(scores + bias, k)
                top = jnp.take_along_axis(scores, experts, axis=-1)
                gates = top / jnp.sum(top, axis=-1, keepdims=True)
            else:
                top, experts = jax.lax.top_k(logits, k)   # (t, k), best first
                gates = jax.nn.softmax(top, axis=-1)
            if self.routed_scale != 1.0:
                gates = gates * self.routed_scale
        if self.latent:
            to_latent = self.param("to_latent", init, (d, width))
            from_latent = self.param("from_latent", init, (width, d))
            with jax.named_scope("moe.latent"):
                latent = jnp.dot(u.reshape(t, d).astype(dt), to_latent.astype(dt))

        with jax.named_scope("moe.dispatch"):
            local = experts - first
            here = (local >= 0) & (local < count)
            local = jnp.where(here, local, count)          # count = "not here"
            onehot = jax.nn.one_hot(local.reshape(-1), count + 1, dtype=jnp.int32)
            sizes = jnp.sum(onehot, axis=0)[:count]
            self.sow("intermediates", "moe_rows_held", jnp.sum(sizes))
            self.sow("intermediates", "moe_rows_max", jnp.max(sizes))
            # rank of each pair inside its expert, token-major
            rank = jnp.sum((jnp.cumsum(onehot, axis=0) - 1) * onehot, axis=-1)
            m = min(k, count)                              # slots a token
            tile_m = tile_rows(t * m, dt)
            rows = buffer_rows(t * m, count, tile_m)
            starts, tile_group, n_tiles = group_tiles(sizes, tile_m, rows)
            place = (jnp.append(starts, 0)[local.reshape(-1)] + rank).reshape(t, k)
            if count < k:
                # a slot is a held expert: place and gate of the choice
                # that fell on it, if one did (one at most)
                chose = local[:, :, None] == jnp.arange(count)   # (t, k, count)
                place = jnp.sum(jnp.where(chose, place[:, :, None], 0), axis=1)
                gates = jnp.sum(jnp.where(chose, gates[:, :, None], 0.0), axis=1)
                here = jnp.any(chose, axis=1)
                self.sow("intermediates", "moe_choices_held_max",
                         jnp.max(jnp.sum(here, axis=1)))
            pairs = jnp.full((rows,), t * m, jnp.int32).at[
                jnp.where(here, place, rows).reshape(-1)].set(
                    jnp.arange(t * m, dtype=jnp.int32), mode="drop",
                    unique_indices=True)
            row = jnp.arange(rows, dtype=jnp.int32)
            live = pairs < t * m
            plan = (jnp.where(live, pairs // m, t + row % _SPARE_ROWS),
                    jnp.where(live, pairs, row % (t * m)),
                    jnp.where(here, place, jnp.arange(t, dtype=jnp.int32)[:, None]),
                    here)
            xs = _dispatch(latent if self.latent else u.reshape(t, d).astype(dt), plan)

        mm = functools.partial(grouped_matmul, tile_group=tile_group,
                               n_tiles=n_tiles, tile_m=tile_m)
        y = expert(mm, xs, *mats)
        with jax.named_scope("moe.combine"):
            out = _combine(y, gates, plan)
        if self.latent:
            with jax.named_scope("moe.latent"):
                out = jnp.dot(out, from_latent.astype(dt))
        if self.shared_d_ff:
            shared = [self.param("shared_" + name, init, shape[1:])
                      for name, shape in _expert_shapes(
                          self.activation, (1, d, self.shared_d_ff),
                          (1, self.shared_d_ff, d))]
            with jax.named_scope("moe.shared"):
                dot = lambda a, w: jnp.dot(a, w.astype(dt))  # noqa: E731
                out = out + expert(dot, u.reshape(t, d).astype(dt), *shared)
        return out.reshape(b, s, d)


def _expert_shapes(activation: str, into, out_of):
    """(name, shape) of an expert's matrices: gate, up, down for a gated
    activation, up, down for relu2."""
    named = [("up", into), ("down", out_of)]
    return named if activation == "relu2" else [("gate", into)] + named


def _expert_fn(activation: str):
    """(product, x, *matrices) -> the experts' output, matrices in the
    order `_expert_shapes` names them."""
    if activation == "relu2":
        return lambda mm, x, up, down: mm(jnp.square(nn.relu(mm(x, up))), down)
    gated = nn.relu if activation == "relu" else nn.silu
    return lambda mm, x, gate, up, down: mm(gated(mm(x, gate)) * mm(x, up), down)


class Mamba2(nn.Module):
    """The Mamba-2 mixer (arXiv:2405.21060; Nemotron-H's form): heads of
    `mamba_head_dim` channels over `mamba_groups` groups of B and C, each
    `mamba_state` wide, head j reading group j * groups // heads.

        [z | xBC | dt] = x W_in
        xBC = silu(causal depthwise conv(xBC) + conv_bias)     kernel mamba_conv
        [x | B | C] = xBC
        y = ssd(x, softplus(dt + dt_bias), -exp(A_log), B, C) + D x
        out = (RMSNorm over each group's channels of (y * silu(z))) W_out

    The gate multiplies before the norm normalises. The scan is
    tpunet.ops.ssd_scan's kernels, in chunks of `mamba_chunk`; the conv and
    the gated norm are plain XLA. No biases but the conv's. Weights:
    in_proj/kernel (d, 2 * inner + 2 * groups * state + heads), conv_kernel
    (mamba_conv, inner + 2 * groups * state), tap i multiplying the position
    mamba_conv - 1 - i back, conv_bias, dt_bias, A_log and D (heads,),
    norm_scale (inner,), out_proj/kernel (inner, d)."""

    spec: LayerSpec

    @nn.compact
    def __call__(self, x):
        from tpunet.ops.ssd_scan import ssd_scan

        spec = self.spec
        b, s, d = x.shape
        heads, p, g, n = (spec.mamba_heads, spec.mamba_head_dim, spec.mamba_groups,
                          spec.mamba_state)
        if heads < 1 or heads % g:
            raise ValueError(f"mamba_heads {heads} must be a positive multiple "
                             f"of mamba_groups {g}")
        if spec.decode:
            raise ValueError(
                "decode=True with a Mamba layer is not supported yet: a step "
                "would carry the layer's recurrent state and its conv window "
                "in a cache of their own (ROADMAP Reach A10); score the full "
                "sequence with decode=False")
        dt_, inner, k = spec.compute_dtype, heads * p, spec.mamba_conv
        conv_dim = inner + 2 * g * n
        with jax.named_scope("mamba.in_proj"):
            zxbc = nn.Dense(2 * inner + 2 * g * n + heads, use_bias=False, dtype=dt_,
                            name="in_proj")(x)
        z, xbc, dt = jnp.split(zxbc, [inner, inner + conv_dim], axis=-1)
        log_dt = jnp.linspace(math.log(1e-3), math.log(1e-1), heads)
        w = self.param("conv_kernel", nn.initializers.normal(k ** -0.5), (k, conv_dim))
        bias = self.param("conv_bias", nn.initializers.zeros, (conv_dim,))
        dt_bias = self.param(  # softplus(dt_bias) spaced from 1e-3 to 1e-1
            "dt_bias", lambda *_: jnp.exp(log_dt) + jnp.log(-jnp.expm1(-jnp.exp(log_dt))),
            (heads,))
        a_log = self.param("A_log", lambda *_: jnp.log(jnp.linspace(1.0, 16.0, heads)),
                           (heads,))
        skip = self.param("D", nn.initializers.ones, (heads,))
        scale = self.param("norm_scale", nn.initializers.ones, (inner,))
        with jax.named_scope("mamba.conv"):
            padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0))).astype(jnp.float32)
            conv = sum(padded[:, i:i + s] * w[i] for i in range(k)) + bias
            xbc = nn.silu(conv).astype(dt_)
        xs, bm, cm = jnp.split(xbc, [inner, inner + g * n], axis=-1)
        with jax.named_scope("mamba.ssd"):
            step = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)
            y = ssd_scan(xs.reshape(b, s, heads, p), step, -jnp.exp(a_log),
                         bm.reshape(b, s, g, n), cm.reshape(b, s, g, n), spec.mamba_chunk)
            y = y.astype(jnp.float32) + skip[:, None] * xs.reshape(b, s, heads, p)
        with jax.named_scope("mamba.gate_norm"):
            y = (y.reshape(b, s, g, inner // g)
                 * nn.silu(z.astype(jnp.float32)).reshape(b, s, g, inner // g))
            y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + spec.norm_eps)
            y = (y.reshape(b, s, inner) * scale).astype(dt_)
        return nn.Dense(d, use_bias=False, dtype=dt_, name="out_proj")(y)


def _grouped_experts(spec: LayerSpec) -> GroupedExperts:
    return GroupedExperts(
        spec.n_experts, spec.moe_top_k, spec.d_ff, spec.moe_held,
        spec.compute_dtype, spec.moe_activation, spec.moe_scoring,
        spec.moe_routed_scale, spec.moe_latent, spec.moe_shared_d_ff, name="moe")


class Block(nn.Module):
    """A pre-norm residual block. With `spec.kind` None: attention, then
    the MLP or the experts, each behind its own norm. With a kind, ONE
    sublayer behind one norm: "M" the Mamba-2 mixer, "*" attention, "E"
    the grouped experts (router and experts read the same normed input)."""

    spec: LayerSpec

    @nn.compact
    def __call__(self, x):
        spec = self.spec
        norm = lambda name: RMSNorm(  # noqa: E731
            spec.norm_eps, spec.norm_unit_offset, spec.compute_dtype, name=name)
        h = norm("norm1")(x)
        if spec.kind == "M":
            return x + Mamba2(spec, name="mamba")(h)
        if spec.kind == "E":
            return x + _grouped_experts(spec)(h, h)
        x = x + SelfAttention(spec, name="attn")(h)
        if spec.kind == "*":
            return x
        if spec.n_experts > 0 and spec.moe_impl == "grouped":
            if spec.moe_router_input not in ("attn_input", "mlp_input"):
                raise ValueError(f"unknown moe_router_input {spec.moe_router_input!r}")
            u = norm("norm2")(x)
            # "attn_input": the router reads the ATTENTION's input, so its
            # choice does not wait for attention
            return x + _grouped_experts(spec)(
                u, h if spec.moe_router_input == "attn_input" else u)
        if spec.n_experts > 0:
            mlp = MoeMlp(spec.n_experts, spec.d_ff, spec.capacity_factor,
                         spec.compute_dtype, top_k=spec.moe_top_k, name="moe")
        else:
            mlp = Mlp(spec.d_ff, spec.compute_dtype, spec.mlp_impl,
                      weight_quant=spec.weight_quant,
                      lora_rank=spec.lora_rank, lora_alpha=spec.lora_alpha,
                      name="mlp")
        return x + mlp(norm("norm2")(x))


class Transformer(nn.Module):
    """Causal decoder-only LM. Tokens (b, s) int32 -> logits (b, s, vocab) f32."""

    vocab: int = 32000
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    d_ff: int = 2048
    n_experts: int = 0            # 0 = dense MLP in every block
    moe_every: int = 2            # every k-th block is MoE (when n_experts>0)
    moe_top_k: int = 1            # experts per token: 1 = Switch, 2 = GShard/Mixtral
    capacity_factor: float = 1.25
    compute_dtype: jnp.dtype = jnp.bfloat16
    remat: bool = False           # rematerialize blocks: trade FLOPs for HBM
    remat_policy: str | None = None  # None=save nothing; "dots" saves matmul
    #   outputs (recompute only cheap elementwise — less HBM relief, near-zero
    #   recompute FLOPs); "dots_no_batch" saves weight-stationary dots only.
    attn_impl: str = "reference"
    mesh: Mesh | None = None
    dp_axis: str | None = "dp"
    sp_axis: str = "sp"
    tp_axis: str | None = None
    n_kv_heads: int | None = None  # < n_heads = grouped-query attention
    mlp_impl: str = "gelu"         # "swiglu" = LLaMA-family FFN
    decode: bool = False           # KV-cache autoregressive inference mode
    attn_window: int | None = None  # sliding-window causal attention (Mistral
    #   -style): each token sees the window most recent positions; flash
    #   kernels prune to O(S*window) FLOPs. reference/flash impls only.
    weight_quant: str | None = None  # "int8" = weight-only quantized matmuls
    #   (inference: pair with tpunet.models.quantize_params on a trained
    #   fp tree; halves the weight HBM traffic decode is bound by)
    prefill: bool = False          # decode=True: route the FIRST cache fill
    #   through the configured attention kernel (flash: O(s) memory, MXU
    #   tiles) instead of the s x cap masked dense einsum; generate() uses a
    #   prefill clone for the whole-prompt call automatically
    per_row_cache: bool = False    # decode=True: per-slot (b,) cache index —
    #   the continuous-batching substrate (tpunet.models.serve.BatchServer)
    decode_ring_cache: bool = True  # attn_window + decode: rolling ring-
    #   buffer KV cache, leaves sized min(window, cap) — bounded memory and
    #   O(window) decode contraction; False = full-capacity masked cache.
    #   speculative_generate keeps the ring when gamma + 1 <= window
    #   (stash/restore rollback), else falls back to the masked cache.
    lora_rank: int = 0             # > 0: rank-r LoRA adapters on every Dense
    #   (tpunet.models.lora: lora_mask to train only A/B, graft_base to
    #   load a base checkpoint, merge_lora to fold back); composes with
    #   weight_quant="int8" (QLoRA: frozen int8 base + fp adapters)
    lora_alpha: float | None = None
    norm_eps: float = 1e-6         # RMSNorm's epsilon, every norm of the model
    norm_unit_offset: bool = False  # norm scales stored as offsets from 1
    rope_theta: float = 10000.0    # the rotary base
    residual_dtype: jnp.dtype | None = None  # the residual stream's type;
    #   None = compute_dtype. float32 keeps the skip additions exact while
    #   every norm hands compute_dtype to the matmuls
    n_pred_heads: int = 1          # > 1: the head gives the next n positions'
    #   logits, (b, s, n, vocab); head j predicts the token at t + 1 + j
    eva_window: int | None = None  # attn_impl="eva" (tpunet.ops.eva_attention)
    eva_chunk: int | None = None
    head_dim: int | None = None    # a head's size; None = d_model // n_heads.
    #   Set, it is free of the model's width: q is n_heads * head_dim wide
    attn_pattern: tuple[tuple[bool, bool], ...] | None = None  # the kinds of
    #   attention layer, repeated over the depth: layer i is
    #   attn_pattern[i % len], a pair (takes attn_window, takes the rotary).
    #   None = every layer takes both, as ((True, True),). A layer without
    #   the rotary has no positional encoding at all (NoPE). Training and
    #   full-sequence scoring; decode with more than one kind raises
    moe_impl: str = "capacity"     # the expert layer of an n_experts > 0
    #   block: "capacity" = MoeMlp (one-hot dispatch into capacity-bounded
    #   slots, ungated experts, tokens over capacity dropped, a sown aux
    #   loss); "grouped" = GroupedExperts (sorted dispatch that drops
    #   nothing, gated ReLU experts of width d_ff, router on the attention's
    #   input, no aux loss)
    moe_held: tuple[int, int] | None = None  # moe_impl="grouped": (first,
    #   count), the experts this model holds of the n_experts it routes
    #   over (expert parallelism's share); None = all
    moe_activation: str = "relu"   # moe_impl="grouped": the gate's function,
    #   "relu" (ReGLU) or "silu" (SwiGLU)
    moe_router_input: str = "attn_input"  # moe_impl="grouped": what the
    #   router reads, "attn_input" (the attention's normalised input) or
    #   "mlp_input" (the expert layer's own, norm2 of the residual)
    qk_norm: bool = False          # an RMSNorm a head on q and on k, before
    #   the rotary, each with a scale of its own (head_dim wide)
    attn_select_top_k: int | None = None  # attention that SELECTS its keys
    #   (tpunet.ops.dsa_attention): an indexer scores every earlier key for
    #   every query, the k best are kept and the softmax runs over those
    #   alone; None = every key the positions allow. attn_impl "flash" (the
    #   kernels) or "reference"; training and full-sequence scoring, decode
    #   raises
    attn_index_heads: int = 0      # the indexer's query heads, over ONE key head
    attn_index_head_dim: int = 0   # and their size
    index_loss_weight: float = 1.0  # what the train step's loss adds of the
    #   indexer's own loss (the mean over the layers of `dsa_index_loss`)
    layer_pattern: str | None = None  # one character a block, n_layers of
    #   them: "M" a Mamba-2 mixer, "*" attention, "E" the grouped experts,
    #   each the block's ONE sublayer (Nemotron-H's hybrid_override_pattern).
    #   None = every block is attention then the MLP or the experts
    mamba_heads: int = 0           # "M" blocks: heads of mamba_head_dim, over
    mamba_head_dim: int = 64       #   mamba_groups groups of B and C, each
    mamba_groups: int = 1          #   mamba_state wide; the causal conv's
    mamba_state: int = 128         #   kernel; the scan's chunk (a multiple of
    mamba_conv: int = 4            #   128 for the compiled kernels)
    mamba_chunk: int = 128
    moe_scoring: str = "softmax"   # moe_impl="grouped" / "E" blocks: "softmax"
    #   over the chosen logits, or "sigmoid" scores chosen with a bias that
    #   takes no gradient and normalised over the chosen (GroupedExperts)
    moe_routed_scale: float = 1.0  # every routed gate times this
    moe_latent: int = 0            # > 0: the experts work in this width
    moe_shared_d_ff: int = 0       # > 0: a shared expert of this many columns
    mtp_pattern: str | None = None  # a multi-token-prediction module of these
    #   block kinds (DeepSeek-V3's form): W_eh [norm(emb(t+1)); norm(h)] over
    #   the last block's output h, the blocks, a norm of its own and the
    #   SHARED head predict the token at t + 2; the mean cross-entropy over
    #   the positions whose t + 2 lies in the sequence is sown as `mtp_loss`.
    #   Training and full-sequence scoring; decode raises
    mtp_loss_weight: float = 0.3   # what the train step's loss adds of mtp_loss

    @nn.nowrap
    def layer_specs(self) -> tuple[LayerSpec, ...]:
        """One LayerSpec a block. The only code that reads the model's
        fields into specs — by NAME, so a field declared on both needs no
        line here — and the only place a layer is made to differ from its
        neighbours: the experts, in every `moe_every`-th block, and the kind
        of attention, by the layer's place in `attn_pattern`."""
        if self.layer_pattern is not None:
            return self._kind_specs(self.layer_pattern)
        base = self._base_spec()
        pattern = self.attn_pattern or ((True, True),)

        def layer(i):
            moe = self.n_experts > 0 and (i + 1) % self.moe_every == 0
            window, rotary = pattern[i % len(pattern)]
            return dataclasses.replace(
                base, n_experts=self.n_experts if moe else 0, rotary=rotary,
                attn_window=self.attn_window if window else None)

        return tuple(layer(i) for i in range(self.n_layers))

    @nn.nowrap
    def mtp_specs(self) -> tuple[LayerSpec, ...]:
        """One LayerSpec a block of the multi-token-prediction module."""
        return self._kind_specs(self.mtp_pattern or "")

    @nn.nowrap
    def _base_spec(self) -> LayerSpec:
        derived = {"head_dim": self.head_dim or self.d_model // self.n_heads,
                   "rotary": True, "kind": None}
        return LayerSpec(**derived, **{
            f.name: getattr(self, f.name)
            for f in dataclasses.fields(LayerSpec) if f.name not in derived})

    @nn.nowrap
    def _kind_specs(self, kinds: str) -> tuple[LayerSpec, ...]:
        """Blocks of one sublayer each, by character; attention's kind by
        the block's place among the attention blocks in `attn_pattern`."""
        if set(kinds) - set("ME*"):
            raise ValueError(f"unknown layer kinds in {kinds!r}: 'M', 'E' or '*'")
        base = self._base_spec()
        pattern = self.attn_pattern or ((True, True),)
        out, seen = [], 0
        for kind in kinds:
            window, rotary = pattern[seen % len(pattern)]
            seen += kind == "*"
            out.append(dataclasses.replace(
                base, kind=kind, n_experts=self.n_experts if kind == "E" else 0,
                rotary=rotary, attn_window=self.attn_window if window else None))
        return tuple(out)

    @nn.nowrap
    def _mtp(self, emb, tokens, last, head, block_cls):
        """The multi-token-prediction module over the last block's output;
        sows its loss."""
        norm = lambda name: RMSNorm(  # noqa: E731
            self.norm_eps, self.norm_unit_offset, self.compute_dtype, name=name)
        following = emb[jnp.roll(tokens, -1, axis=1)].astype(last.dtype)
        m = _dense(self.d_model, self.compute_dtype, "mtp_proj", None)(
            jnp.concatenate([norm("mtp_norm_e")(following), norm("mtp_norm_h")(last)],
                            axis=-1)).astype(last.dtype)
        for j, spec in enumerate(self.mtp_specs()):
            m = block_cls(spec, name=f"mtp_block{j}")(m)
        logits = head(norm("mtp_norm_f")(m)).astype(jnp.float32)
        self.sow("intermediates", "mtp_loss", _mtp_loss(logits, tokens))

    @nn.compact
    def __call__(self, tokens, train: bool = False, features_only: bool = False):
        # features_only: return the final normed hidden states (b, s, d) in
        # compute_dtype instead of logits — the input the blockwise fused
        # cross-entropy (tpunet.ops.blockwise_cross_entropy) pairs with the
        # lm_head kernel so the (b, s, vocab) logits are never materialized.
        del train  # no dropout in this family; kept for trainer signature
        if self.weight_quant not in (None, "int8"):
            raise ValueError(f"unknown weight_quant {self.weight_quant!r}")
        if self.weight_quant is not None:
            if self.n_experts > 0:
                raise ValueError(
                    "weight_quant does not cover MoE expert einsum weights; "
                    "use a dense model or weight_quant=None")
            if features_only:
                raise ValueError(
                    "weight_quant is incompatible with features_only: the "
                    "blockwise fused cross-entropy reads an fp lm_head "
                    "kernel from the params tree")
        if self.lora_rank > 0 and features_only:
            raise ValueError(
                "lora_rank is incompatible with features_only: the "
                "blockwise fused cross-entropy reads params['lm_head']"
                "['kernel'], but the adapted tree nests it under 'base' "
                "(and the lm_head adapters would be silently dropped) - "
                "merge_lora first, or train without fused xent")
        if self.moe_impl not in ("capacity", "grouped"):
            raise ValueError(f"unknown moe_impl {self.moe_impl!r}")
        if self.decode and len(set(self.attn_pattern or ())) > 1:
            raise ValueError(
                "decode=True with more than one kind of layer in attn_pattern "
                "is not supported yet: the servers keep one kind of KV cache "
                "for every layer, and a ring cache beside a full one is "
                "ROADMAP Reach A2's serving half; score the full sequence "
                "with decode=False")
        if self.n_pred_heads > 1 and (features_only or self.decode):
            raise ValueError(
                "n_pred_heads > 1 has no fused cross-entropy and no decode "
                "path: both read one position's logits from the head")
        if self.layer_pattern is not None and len(self.layer_pattern) != self.n_layers:
            raise ValueError(f"layer_pattern {self.layer_pattern!r} names "
                             f"{len(self.layer_pattern)} blocks, n_layers is {self.n_layers}")
        if self.mtp_pattern and (self.decode or features_only or self.n_pred_heads > 1):
            raise ValueError(
                "mtp_pattern is not supported with decode=True, features_only "
                "or n_pred_heads > 1: the module reads the next token's "
                "embedding and the last block's output over the whole "
                "sequence, and gives its own loss; as a draft source for "
                "speculative_generate it is ROADMAP's")
        emb = self.param(
            "embed", nn.initializers.normal(0.02), (self.vocab, self.d_model)
        )
        x = emb[tokens].astype(self.residual_dtype or self.compute_dtype)
        # remat drops block activations in the forward pass and recomputes
        # them in the backward — the standard long-context memory lever
        # (sequence activations dominate HBM; FLOPs are MXU-cheap).
        policies = {
            None: None,
            "dots": jax.checkpoint_policies.dots_saveable,
            "dots_no_batch":
                jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        }
        if self.remat_policy not in policies:
            # Validated even when remat is off / decoding — a typo'd policy
            # silently doing nothing would corrupt memory-sweep conclusions.
            raise ValueError(f"unknown remat_policy {self.remat_policy!r}")
        if self.decode or not self.remat:
            block_cls = Block
        else:
            pol = policies[self.remat_policy]
            block_cls = nn.remat(Block, policy=pol) if pol else nn.remat(Block)
        for i, spec in enumerate(self.layer_specs()):
            x = block_cls(spec, name=f"block{i}")(x)
        last = x
        x = RMSNorm(self.norm_eps, self.norm_unit_offset, self.compute_dtype,
                    name="norm_f")(x)
        if features_only:
            if self.is_initializing():
                # The lm_head param must still exist (fused-xent callers
                # read it from the params tree): materialize the kernel with
                # a 1-token touch instead of the full matmul.
                nn.Dense(self.vocab, use_bias=False, dtype=self.compute_dtype,
                         name="lm_head")(x[..., :1, :])
            return x.astype(self.compute_dtype)
        head = _dense(self.vocab * self.n_pred_heads, self.compute_dtype,
                      "lm_head", self.weight_quant, self.lora_rank, self.lora_alpha)
        logits = head(x).astype(jnp.float32)
        if self.mtp_pattern:
            with jax.named_scope("mtp"):
                self._mtp(emb, tokens, last, head, block_cls)
        if self.n_pred_heads > 1:
            logits = logits.reshape(*logits.shape[:-1], self.n_pred_heads,
                                    self.vocab)
        return logits


def _mtp_loss(logits, tokens):
    """Mean cross-entropy of logits (b, s, vocab) at position t against the
    token at t + 2, over the positions where that lies in the sequence."""
    s = tokens.shape[1]
    target = jnp.roll(tokens, -2, axis=1)
    nll = (jax.nn.logsumexp(logits, axis=-1)
           - jnp.take_along_axis(logits, target[..., None], axis=-1)[..., 0])
    inside = jnp.arange(s) < s - 2
    return jnp.sum(jnp.where(inside, nll, 0.0)) / (tokens.shape[0] * (s - 2))


def transformer_partition_rules(
    tp_axis: str | None = "mdl", ep_axis: str | None = None
) -> list[tuple[str, P]]:
    """Path-regex → PartitionSpec rules (first match wins; no match =
    replicated). Megatron TP over `tp_axis` (None = no TP); MoE experts over
    `ep_axis` (None = experts replicated)."""
    ep = ep_axis
    return [
        (r".*attn/(q|k|v)/kernel", P(None, tp_axis)),
        (r".*attn/out/kernel", P(tp_axis, None)),
        (r".*mlp/(up|gate)/kernel", P(None, tp_axis)),
        (r".*mlp/down/kernel", P(tp_axis, None)),
        (r".*moe/router", P()),
        (r".*moe/wi", P(ep, None, tp_axis)),
        (r".*moe/wo", P(ep, tp_axis, None)),
        (r".*embed", P(tp_axis, None)),
        (r".*lm_head/kernel", P(None, tp_axis)),
        # weight_quant="int8" trees: q shards exactly like its kernel; the
        # per-output-channel scale shards with the OUTPUT dim — along
        # tp_axis for column-parallel kernels, replicated for row-parallel
        # ones (whose output dim is unsharded). Correctness under TP is
        # free either way: the scale is per-column, so it distributes over
        # the row-parallel psum — (Σ_p x_p @ q_p) · s == Σ_p (x_p @ q_p · s).
        (r".*attn/(q|k|v)/q", P(None, tp_axis)),
        (r".*attn/(q|k|v)/scale", P(tp_axis)),
        (r".*attn/out/q", P(tp_axis, None)),
        (r".*attn/out/scale", P()),
        (r".*mlp/(up|gate)/q", P(None, tp_axis)),
        (r".*mlp/(up|gate)/scale", P(tp_axis)),
        (r".*mlp/down/q", P(tp_axis, None)),
        (r".*mlp/down/scale", P()),
        (r".*lm_head/q", P(None, tp_axis)),
        (r".*lm_head/scale", P(tp_axis)),
        # lora_rank>0 trees: base kernels nest one level deeper ("base/"),
        # same specs as their plain forms. Adapters follow the Megatron
        # LoRA convention: for a column-parallel W, A (in, r) replicates
        # and B (r, out) shards its output dim; for a row-parallel W,
        # A (in, r) shards its input dim and B replicates - each adapter
        # matmul then lives on the same shards as its base matmul.
        (r".*attn/(q|k|v)/base/kernel", P(None, tp_axis)),
        (r".*attn/out/base/kernel", P(tp_axis, None)),
        (r".*mlp/(up|gate)/base/kernel", P(None, tp_axis)),
        (r".*mlp/down/base/kernel", P(tp_axis, None)),
        (r".*lm_head/base/kernel", P(None, tp_axis)),
        (r".*attn/(q|k|v)/base/q", P(None, tp_axis)),
        (r".*attn/(q|k|v)/base/scale", P(tp_axis)),
        (r".*attn/out/base/q", P(tp_axis, None)),
        (r".*attn/out/base/scale", P()),
        (r".*mlp/(up|gate)/base/q", P(None, tp_axis)),
        (r".*mlp/(up|gate)/base/scale", P(tp_axis)),
        (r".*mlp/down/base/q", P(tp_axis, None)),
        (r".*mlp/down/base/scale", P()),
        (r".*lm_head/base/q", P(None, tp_axis)),
        (r".*lm_head/base/scale", P(tp_axis)),
        (r".*(attn/(q|k|v)|mlp/(up|gate)|lm_head)/lora_a", P()),
        (r".*(attn/(q|k|v)|mlp/(up|gate)|lm_head)/lora_b", P(None, tp_axis)),
        (r".*(attn/out|mlp/down)/lora_a", P(tp_axis, None)),
        (r".*(attn/out|mlp/down)/lora_b", P()),
    ]
