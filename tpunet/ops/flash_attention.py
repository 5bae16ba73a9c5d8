"""Flash attention — Pallas TPU kernels with online softmax, fwd + bwd.

The reference repo (bagua-net) is pure transport and has no kernels; this op
exists because our framework's model layer (transformer family, long-context
ring attention) needs the attention hot op to be MXU-shaped: blockwise QK^T
and PV matmuls with f32 accumulators, never materializing the (Sq, Sk) score
matrix in HBM.

Design notes (TPU-first):
  * grid = (batch*heads, Sq/block_q); each program streams the K/V sequence
    blockwise through VMEM with a `fori_loop`, carrying the online-softmax
    state (m, l, acc) functionally.
  * causal masking prunes the k-loop upper bound per q-block (no wasted
    MXU work on fully-masked blocks); the visited blocks are masked
    elementwise.
  * the (block_q, block_k) tile of one loop step is chosen from the call's
    shapes by `_plan` (512 x 512 at long sequences: a visit's fixed cost,
    not the MXU, bounds a 128 x 128 step); forward and backward both ask it.
  * backward pass: FlashAttention-2 style blockwise kernels. The forward
    additionally emits the per-row logsumexp; the backward recomputes
    P = exp(S - lse) within blocks (O(S) memory, no stored score matrix)
    in two kernels — dQ (grid over q-blocks) and dK/dV (grid over k-blocks,
    causal lower bound prunes fully-masked q-blocks). Training keeps the
    flash memory win instead of falling back to the O(S^2) einsum VJP.
  * `interpret` defaults to "auto": the Pallas interpreter on CPU (tests),
    compiled Mosaic on TPU.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# Mosaic requires the last two dims of every block to be divisible by the
# (8, 128) f32 tile (or to equal the full array dims). A natural (b*h, sq)
# logsumexp with (1, block_q) blocks violates the sublane rule — the round-2
# on-chip failure. We instead carry lse/delta as (b*h, LSE_SUBLANES, sq) with
# the value broadcast across LSE_SUBLANES=8 sublanes: blocks are then
# (1, 8, block_q) = exactly one legal tile, at 8x memory for a tiny array
# (vs. the 128x lane-broadcast layout jax's reference kernel uses).
LSE_SUBLANES = 8


def attention_reference(q, k, v, causal: bool = False,
                        window: int | None = None):
    """Plain softmax attention, f32 internally. Shapes (B, S, H, D).
    window (requires causal): each query attends only the `window` most
    recent positions including itself — q_pos - k_pos < window."""
    dt = q.dtype
    scale = 1.0 / math.sqrt(q.shape[-1])
    prec = _dot_precision(dt)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32) * scale,
                   k.astype(jnp.float32), precision=prec)
    if window is not None and not causal:
        raise ValueError("window requires causal=True")
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        qpos = jnp.arange(sq)[:, None]
        kpos = jnp.arange(sk)[None, :]
        keep = qpos >= kpos
        if window is not None:
            keep &= (qpos - kpos) < window
        s = jnp.where(keep, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32), precision=prec)
    return o.astype(dt)


# The loop bounds of one program: the causal diagonal and the sliding
# window prune the tiles it visits to those that hold a visible (query, key)
# pair; the elementwise mask trims the rest inside a tile. On Python ints
# (the plan's count) and on traced ints (the kernels' bounds) alike.

def _k_tiles(i, block_q, block_k, seq_k, causal, window, maximum=jnp.maximum):
    """[lo, hi): the k-tiles q-block i visits (forward, dQ)."""
    if not causal:
        return 0, seq_k // block_k
    # Last k-block that the final row of this q-block may attend to.
    hi = pl.cdiv((i + 1) * block_q, block_k)
    if window is None:
        return 0, hi
    # First k-block any row of this q-block still sees: the FIRST row's
    # oldest key is i*bq - (window-1); the lower bound must cover it.
    return maximum(i * block_q - (window - 1), 0) // block_k, hi


def _q_tiles(j, block_q, block_k, seq_q, causal, window, minimum=jnp.minimum):
    """[lo, hi): the q-tiles k-block j visits (dK/dV)."""
    num_qb = seq_q // block_q
    if not causal:
        return 0, num_qb
    lo = (j * block_k) // block_q  # first q-block with a row attending here
    if window is None:
        return lo, num_qb
    # The window also bounds ABOVE: the newest query still seeing this
    # k-block's newest key j*bk + bk - 1 is that + window - 1.
    return lo, minimum(
        num_qb, pl.cdiv(j * block_k + block_k - 1 + window, block_q))


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_q: int,
                  block_k: int, seq_k: int, causal: bool, scale: float,
                  precision, window: int | None = None):
    """One (batch*head, q-block) program. Refs: q (1, block_q, D),
    k/v (1, seq_k, D), o (1, block_q, D), lse (1, LSE_SUBLANES, block_q)."""
    qi = pl.program_id(1)
    q = q_ref[0, :, :].astype(jnp.float32) * scale
    head_dim = q.shape[-1]

    j_start, num_kb = _k_tiles(qi, block_q, block_k, seq_k, causal, window)

    def body(j, carry):
        acc, m, l = carry
        kb = k_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        vb = v_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, kb, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision,
        )  # (block_q, block_k)
        if causal:
            s = _causal_mask(s, qi * block_q, j * block_k, block_q, block_k,
                             window)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=1, keepdims=True)
        acc_new = acc * alpha + jax.lax.dot_general(
            p, vb, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision,
        )
        return acc_new, m_new, l_new

    acc0 = jnp.zeros((block_q, head_dim), jnp.float32)
    m0 = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc, m, l = jax.lax.fori_loop(j_start, num_kb, body, (acc0, m0, l0))
    o_ref[0, :, :] = (acc / l).astype(o_ref.dtype)
    # Per-row logsumexp: the only softmax state the backward needs.
    lse_row = m[:, 0] + jnp.log(l[:, 0])  # (block_q,)
    lse_ref[0, :, :] = jnp.broadcast_to(lse_row[None, :], (LSE_SUBLANES, block_q))


def _causal_mask(s, q_start, k_start, block_q, block_k, window=None):
    qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    keep = qpos >= kpos
    if window is not None:
        keep &= (qpos - kpos) < window
    return jnp.where(keep, s, NEG_INF)


def _flash_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                     *, block_q: int, block_k: int, seq_k: int, causal: bool,
                     scale: float, precision, window: int | None = None):
    """dQ, one (batch*head, q-block) program: streams k/v blockwise and
    accumulates dq = sum_j dS_ij @ K_j with P recomputed from the lse."""
    qi = pl.program_id(1)
    q = q_ref[0, :, :].astype(jnp.float32)
    do = do_ref[0, :, :].astype(jnp.float32)
    lse = lse_ref[0, 0, :][:, None]
    delta = delta_ref[0, 0, :][:, None]
    head_dim = q.shape[-1]

    j_start, num_kb = _k_tiles(qi, block_q, block_k, seq_k, causal, window)

    def body(j, dq):
        kb = k_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        vb = v_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        s = scale * jax.lax.dot_general(
            q, kb, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision,
        )
        if causal:
            s = _causal_mask(s, qi * block_q, j * block_k, block_q, block_k,
                             window)
        p = jnp.exp(s - lse)  # masked entries underflow to exactly 0
        dp = jax.lax.dot_general(
            do, vb, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision,
        )
        ds = p * (dp - delta) * scale
        return dq + jax.lax.dot_general(
            ds, kb, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision,
        )

    dq = jax.lax.fori_loop(j_start, num_kb, body,
                           jnp.zeros((block_q, head_dim), jnp.float32))
    dq_ref[0, :, :] = dq.astype(dq_ref.dtype)


def _flash_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dk_ref, dv_ref, dk_acc, dv_acc, *, block_q: int,
                      block_k: int, seq_q: int, causal: bool, scale: float,
                      precision, group: int = 1, window: int | None = None):
    """dK/dV, one (batch*KV-head, k-block, group-member) program: streams
    q/do blockwise. dv = sum_i P_ij^T @ dO_i; dk = sum_i dS_ij^T @ Q_i.

    Under GQA the third grid axis walks the `group` of q heads sharing this
    kv head — the repeat-then-sum transpose of the forward's broadcast,
    computed without materializing group-repeated K/V and WITHOUT staging
    the whole group in VMEM at once (a (group, sq, d) block at group=8,
    sq=8k, bf16 would be 16 MB — over VMEM; per-program blocks here stay
    single-head). g is the fastest axis, so the dk/dv output blocks are
    revisited consecutively; f32 VMEM scratch carries the partial sums
    across the g-steps and the output is written once, on the last member
    (full precision regardless of the output dtype)."""
    kj = pl.program_id(1)
    g = pl.program_id(2)
    kb = k_ref[0, :, :].astype(jnp.float32)
    vb = v_ref[0, :, :].astype(jnp.float32)
    i_start, i_end = _q_tiles(kj, block_q, block_k, seq_q, causal, window)

    def body(i, carry):
        dk, dv = carry
        qb = q_ref[0, pl.ds(i * block_q, block_q), :].astype(jnp.float32)
        dob = do_ref[0, pl.ds(i * block_q, block_q), :].astype(jnp.float32)
        lse_i = lse_ref[0, 0, pl.ds(i * block_q, block_q)][:, None]
        delta_i = delta_ref[0, 0, pl.ds(i * block_q, block_q)][:, None]
        s = scale * jax.lax.dot_general(
            qb, kb, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision,
        )
        if causal:
            s = _causal_mask(s, i * block_q, kj * block_k, block_q, block_k,
                             window)
        p = jnp.exp(s - lse_i)
        dv = dv + jax.lax.dot_general(
            p, dob, dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision,
        )
        dp = jax.lax.dot_general(
            dob, vb, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision,
        )
        ds = p * (dp - delta_i) * scale
        dk = dk + jax.lax.dot_general(
            ds, qb, dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision,
        )
        return dk, dv

    zeros = jnp.zeros((kb.shape[0], kb.shape[1]), jnp.float32)
    dk, dv = jax.lax.fori_loop(i_start, i_end, body, (zeros, zeros))

    @pl.when(g == 0)
    def _init():
        dk_acc[...] = dk
        dv_acc[...] = dv

    @pl.when(g > 0)
    def _accum():
        dk_acc[...] += dk
        dv_acc[...] += dv

    @pl.when(g == group - 1)
    def _flush():
        dk_ref[0, :, :] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, :, :] = dv_acc[...].astype(dv_ref.dtype)


def _auto_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _dot_precision(dtype):
    """MXU passes are bf16: f32 inputs need HIGHEST (multi-pass) to keep f32
    accuracy vs the XLA reference; bf16 inputs carry no extra bits to keep."""
    if dtype == jnp.float32:
        return jax.lax.Precision.HIGHEST
    return jax.lax.Precision.DEFAULT


def _normalize_blocks(sq, sk, block_q, block_k, interpret, dtype):
    """Clamp block sizes to Mosaic-legal values for compiled mode.

    The lse/delta blocks put block_q on the LANE dim, so compiled kernels
    need block_q % 128 == 0 or block_q == sq. block_k sits on the k/v
    SUBLANE dim, whose min tile depends on dtype (8 f32 / 16 bf16 / 32
    int8 — i.e. 32 bytes), so block_k must be a multiple of that or equal
    sk. A block equal to the full array dim is always legal, so full-dim
    blocks are the universal repair (at higher VMEM cost — only taken for
    odd shapes). Interpret mode has no such constraints — tests
    deliberately use tiny blocks there.
    """
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    if interpret:
        return block_q, block_k
    min_sublane = 32 // jnp.dtype(dtype).itemsize
    if block_q % 128 and block_q != sq:
        block_q = 128 if sq % 128 == 0 else sq
    if block_k % min_sublane and block_k != sk:
        block_k = 128 if sk % 128 == 0 else sk
    return block_q, block_k


class FlashPlan(NamedTuple):
    """What `_plan` decided for one call, and what engages with it."""
    block_q: int
    block_k: int
    tiles: int  # (q-block, k-block) tiles one head visits


# The largest tile side the plan picks by itself, from the sweep on the chip
# at b2 s8192 h32 kv8 d128 bf16 window 4096 (PERF.md section 6, PR 27). The
# kernels stage whole sequences (K and V; q and do in dK/dV), and that, not
# the tile, is what runs a long sequence out of VMEM: compiled for a v5e,
# every shape that fits with 128 x 128 tiles fits with 512 x 512.
_TILE_CEILING = 512


def _auto_block(seq):
    """A tile side for `seq`: one tile where the sequence is short, else
    the largest of _TILE_CEILING, half of it, ..., 128 that divides it. A
    sequence over 128 that no multiple of 128 divides keeps 128, which does
    not tile it: such shapes take the einsum path, as they always did."""
    if seq <= 128 or (seq <= _TILE_CEILING and seq % 128 == 0):
        return seq
    block = _TILE_CEILING
    while block > 128 and seq % block:
        block //= 2
    return block


def _plan(sq, sk, dtype, causal, window, block_q=None, block_k=None,
          interpret=False):
    """The tiles of one flash_attention call, from its shapes alone; None
    where they do not tile and the einsum path runs. Forward and backward
    both ask here, so they cannot disagree.

    An explicit block_q/block_k wins (tests use tiny ones in interpret
    mode, the sweep its grid); one given alone is also the other's value.
    Left to the plan, a tile side is the whole sequence where that is short
    and else the largest of _TILE_CEILING, half of it, ..., 128 that divides
    the sequence. The einsum path is taken for ragged tiling, a mixed block
    ratio under causal, and causal cross-attention (sq != sk): the kernels'
    causal loop bounds assume aligned q/k positions and
    block_q % block_k == 0."""
    if block_q is None and block_k is None:
        block_q, block_k = _auto_block(sq), _auto_block(sk)
    else:
        block_q = block_k if block_q is None else block_q
        block_k = block_q if block_k is None else block_k
    block_q, block_k = _normalize_blocks(sq, sk, block_q, block_k, interpret,
                                         dtype)
    if (sq % block_q or sk % block_k
            or (causal and (block_q % block_k or sq != sk))):
        return None
    tiles = 0
    for i in range(sq // block_q):
        lo, hi = _k_tiles(i, block_q, block_k, sk, causal, window, max)
        tiles += hi - lo
    return FlashPlan(block_q, block_k, tiles)


def _flatten_heads(x):
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _unflatten_heads(xf, b, h):
    bh, s, d = xf.shape
    return xf.reshape(b, h, s, d).transpose(0, 2, 1, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal: bool = False,
                    block_q: int | None = None, block_k: int | None = None,
                    interpret: bool | None = None, window: int | None = None):
    """Flash attention. q: (batch, seq, heads, head_dim); k/v may carry
    FEWER heads (grouped-query attention — heads % kv_heads == 0): each
    q-head program's K/V BlockSpec index_map points at its kv head
    (bh // group), so the group-repeated K/V never exists in HBM — the kv
    tensors stream at 1/group the bandwidth of the MHA equivalent. Returns
    q-shaped output.

    window (requires causal): sliding-window attention — each query sees
    only the `window` most recent positions including itself. The kernels
    prune the k-loop at BOTH ends (and the dK/dV q-loop symmetrically), so
    compute scales O(S·window) instead of O(S²/2) — the long-context FLOPs
    lever when full attention isn't needed.

    block_q, block_k: the (q, k) tile of scores one loop step computes. Left
    None, `_plan` chooses them from the shapes; an explicit value wins.

    Falls back to the reference einsum path (with an explicit kv repeat for
    GQA) when the sequence lengths don't tile evenly — ragged tails are a
    later kernel feature, not a behavioral gap; results are identical
    either way.
    """
    o, _ = _flash_fwd_impl(q, k, v, causal, block_q, block_k, interpret,
                           window)
    return o


def _repeat_kv(x, group: int):
    return jnp.repeat(x, group, axis=2) if group > 1 else x


def _gqa_group(q, k):
    h, hk = q.shape[2], k.shape[2]
    if h % hk:
        raise ValueError(f"q heads {h} not divisible by kv heads {hk}")
    return h // hk


def _flash_fwd_impl(q, k, v, causal, block_q, block_k, interpret,
                    window=None):
    """Returns (o, lse) — lse is None when the einsum fallback was taken."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    group = _gqa_group(q, k)
    if window is not None and (not causal or window < 1):
        raise ValueError("window requires causal=True and window >= 1")
    if interpret is None:
        interpret = _auto_interpret()
    plan = _plan(sq, sk, q.dtype, causal, window, block_q, block_k, interpret)
    if plan is None:
        return attention_reference(q, _repeat_kv(k, group),
                                   _repeat_kv(v, group), causal, window), None
    block_q, block_k = plan.block_q, plan.block_k

    # (B, S, H, D) -> (B*H, S, D): grid programs are independent per head.
    qf = _flatten_heads(q)
    kf = _flatten_heads(k)  # (B*Hkv, S, D) under GQA
    vf = _flatten_heads(v)

    kernel = functools.partial(
        _flash_kernel, block_q=block_q, block_k=block_k, seq_k=sk,
        causal=causal, scale=1.0 / math.sqrt(d), precision=_dot_precision(q.dtype),
        window=window,
    )
    of, lse = pl.pallas_call(
        kernel,
        grid=(b * h, sq // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, i: (bh, i, 0)),
            pl.BlockSpec((1, sk, d), lambda bh, i: (bh // group, 0, 0)),
            pl.BlockSpec((1, sk, d), lambda bh, i: (bh // group, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, i: (bh, i, 0)),
            pl.BlockSpec((1, LSE_SUBLANES, block_q), lambda bh, i: (bh, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, LSE_SUBLANES, sq), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return _unflatten_heads(of, b, h), lse


def _flash_fwd(q, k, v, causal, block_q, block_k, interpret, window=None):
    o, lse = _flash_fwd_impl(q, k, v, causal, block_q, block_k, interpret,
                             window)
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, block_q, block_k, interpret, window, res, g):
    q, k, v, o, lse = res
    group = _gqa_group(q, k)
    if lse is None:  # forward took the einsum fallback (ragged shapes)
        def ref(q, k, v):
            return attention_reference(
                q, _repeat_kv(k, group), _repeat_kv(v, group), causal, window
            )  # vjp of the repeat sums each kv head's group automatically

        _, vjp = jax.vjp(ref, q, k, v)
        return vjp(g)
    if interpret is None:
        interpret = _auto_interpret()

    b, sq, h, d = q.shape
    sk = k.shape[1]
    hk = k.shape[2]
    # The forward's plan again: it saved an lse (and did not take the einsum
    # path) only for shapes where the plan gives tiles.
    block_q, block_k = _plan(sq, sk, q.dtype, causal, window, block_q,
                             block_k, interpret)[:2]
    scale = 1.0 / math.sqrt(d)

    qf, kf, vf = _flatten_heads(q), _flatten_heads(k), _flatten_heads(v)
    of, dof = _flatten_heads(o), _flatten_heads(g)
    # delta_i = rowsum(dO_i * O_i): the softmax-jacobian correction term,
    # cheap elementwise work XLA fuses — no kernel needed. Broadcast into the
    # same sublane-replicated layout the kernels require for lse.
    delta = jnp.sum(dof.astype(jnp.float32) * of.astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta[:, None, :], (b * h, LSE_SUBLANES, sq))

    dq_kernel = functools.partial(
        _flash_dq_kernel, block_q=block_q, block_k=block_k, seq_k=sk,
        causal=causal, scale=scale, precision=_dot_precision(q.dtype),
        window=window,
    )
    dqf = pl.pallas_call(
        dq_kernel,
        grid=(b * h, sq // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, i: (bh, i, 0)),
            pl.BlockSpec((1, sk, d), lambda bh, i: (bh // group, 0, 0)),
            pl.BlockSpec((1, sk, d), lambda bh, i: (bh // group, 0, 0)),
            pl.BlockSpec((1, block_q, d), lambda bh, i: (bh, i, 0)),
            pl.BlockSpec((1, LSE_SUBLANES, block_q), lambda bh, i: (bh, 0, i)),
            pl.BlockSpec((1, LSE_SUBLANES, block_q), lambda bh, i: (bh, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, i: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        interpret=interpret,
    )(qf, kf, vf, dof, lse, delta)

    dkv_kernel = functools.partial(
        _flash_dkv_kernel, block_q=block_q, block_k=block_k, seq_q=sq,
        causal=causal, scale=scale, precision=_dot_precision(q.dtype),
        group=group, window=window,
    )
    # Grid over KV heads x k-blocks x group members (g fastest, so each
    # dk/dv output block's revisits are consecutive and the VMEM scratch
    # accumulates across them). Each program stages ONE q head's rows —
    # q-head row for member g of kv head bkv is bkv*group + g in the
    # head-flattened layout (a batch's heads are adjacent).
    dkf, dvf = pl.pallas_call(
        dkv_kernel,
        grid=(b * hk, sk // block_k, group),
        in_specs=[
            pl.BlockSpec((1, sq, d), lambda bkv, j, g: (bkv * group + g, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda bkv, j, g: (bkv, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda bkv, j, g: (bkv, j, 0)),
            pl.BlockSpec((1, sq, d), lambda bkv, j, g: (bkv * group + g, 0, 0)),
            pl.BlockSpec((1, LSE_SUBLANES, sq),
                         lambda bkv, j, g: (bkv * group + g, 0, 0)),
            pl.BlockSpec((1, LSE_SUBLANES, sq),
                         lambda bkv, j, g: (bkv * group + g, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda bkv, j, g: (bkv, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda bkv, j, g: (bkv, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * hk, sk, d), k.dtype),
            jax.ShapeDtypeStruct((b * hk, sk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf, dof, lse, delta)

    return (
        _unflatten_heads(dqf, b, h),
        _unflatten_heads(dkf, b, hk),
        _unflatten_heads(dvf, b, hk),
    )


flash_attention.defvjp(_flash_fwd, _flash_bwd)
