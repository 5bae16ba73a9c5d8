"""Attention that selects its keys — Pallas TPU kernels and the plain forms.

Every other attention of this package decides from POSITIONS which keys a
query sees (the causal diagonal, a window, EVA's chunks). Here the data
decides: a small "indexer" scores every earlier key for every query, the
`top_k` best are kept, and the main attention's softmax runs over those
alone (the mechanism of the DeepSeek-V3.2-Exp report). Four steps, each a
function of this module with its plain form beside its kernels;
`SelfAttention` strings them together:

  * `index_scores`: I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s]) over
    the indexer's heads j, ONE key head. Kernel `dsa_index_fwd`: tiles of
    qI against kI on the MXU, relu and the weighted sum over heads on the
    VPU, causal tiles only (a tile above the diagonal is neither computed
    nor written: its block index is the diagonal tile's, so nothing moves).
    No backward of its own: the scores are ranked (no gradient) and enter
    the indexer's loss, whose backward is written by hand below.
  * `select`: S_t = the min(t + 1, top_k) keys s <= t of largest I[t, s],
    ties to the lower index (`jax.lax.top_k`'s rule), as an int8 mask
    (b, s, s); kernel `dsa_select`. EXACT: the k-th largest score is found by
    bisection on the scores' bit patterns (a float32's bits, sign-folded,
    order as the floats do), and the ties AT that score by a second
    bisection on the key index (at b2 s8192 `jax.lax.top_k` takes three
    times as long on the chip, and its indices are no mask yet).
  * `selected_attention`: softmax of q.k/sqrt(d) over S_t alone, grouped-
    query. Kernels `dsa_attn_fwd`, `dsa_attn_dq`, `dsa_attn_dkv`: the flash
    kernels' loops with one more operand, the mask's (block_q, seq) or
    (seq, block_k) slab, so they visit every causal tile and mask inside it
    (random weights scatter a query's keys over all of them). The mask is a
    constant of the backward pass.
  * `index_loss`: L_I = mean_t KL(p[t, .] || softmax_{S_t}(I[t, .])), p the
    mean over the heads of the main attention's probabilities on S_t,
    detached. Its backward is (softmax_{S_t}(I) - p) on the selected pairs
    pushed through the score's own products, computed WITH the loss (the
    loss is a scalar, so its gradient is the same array whatever the
    cotangent, times it) instead of by autodiff, which would keep every
    block's (heads, block, seq) products for the way back. Kernels
    `dsa_kl_fwd`, `dsa_index_dq`, `dsa_index_dk`; the plain XLA form a
    block of queries at a time (`_kl_pass`) is what the tests compare them
    with, and 300 times slower on the chip.

The kernels' names are the HLO instructions' names (`pallas_call(name=)`),
which is how the benchmark's readers find them on the device's timeline.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpunet.ops.flash_attention import (LSE_SUBLANES, NEG_INF, _auto_block,
                                        _auto_interpret, _dot_precision,
                                        _flatten_heads, _k_tiles, _q_tiles,
                                        _unflatten_heads)

_KL_BLOCK = 512  # queries a step of the loss's plain pass: (heads, 512, seq) floats
_KL_ROWS = 128  # queries a program of `dsa_kl_fwd`: p's (128, seq) slab in VMEM
_SELECT_ROWS = 256  # queries a program of `dsa_select`: (256, seq) scores in VMEM
_INT_MIN = -(2 ** 31)


def _block(seq: int, block: int | None) -> int:
    block = _auto_block(seq) if block is None else min(block, seq)
    if seq % block:
        raise ValueError(f"a sequence of {seq} is not tiled by blocks of {block}")
    return block


def _dot(a, b, dims):
    """Operands as they come (bfloat16 in the model) with float32
    accumulation; float32 operands multiplied as float32."""
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32,
                               precision=_dot_precision(a.dtype))


def _params(axes: int, vmem_mib: int = 64):
    """Every axis in order (blocks are revisited along the innermost), and
    a share of a v5e core's 128 MiB of VMEM: the mask's slab (block x seq
    int8, twice) beside a head's whole K and V."""
    return pltpu.CompilerParams(dimension_semantics=("arbitrary",) * axes,
                                vmem_limit_bytes=vmem_mib * 1024 * 1024)


# -- the indexer's scores ------------------------------------------------------------

def index_scores_reference(qi, ki, w):
    """qi: (b, s, heads, d); ki: (b, s, d); w: (b, s, heads) float32 ->
    (b, s, s) float32, every pair (the caller masks)."""
    dots = jnp.einsum("bqjd,bsd->bjqs", qi, ki, preferred_element_type=jnp.float32)
    return jnp.einsum("bjqs,bqj->bqs", jax.nn.relu(dots), w.astype(jnp.float32))


def _index_kernel(q_ref, k_ref, w_ref, o_ref, *, heads: int):
    """One (row, q-block, k-block) program. q (1, heads, bq, d), k (1, bk, d),
    w (1, bq, heads) float32, o (1, bq, bk) float32."""
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j <= i)
    def _tile():
        k = k_ref[0]
        acc = jnp.zeros(o_ref.shape[1:], jnp.float32)
        for h in range(heads):
            dots = _dot(q_ref[0, h], k, ((1,), (1,)))
            acc = acc + w_ref[0, :, h:h + 1] * jnp.maximum(dots, 0.0)
        o_ref[0] = acc


def index_scores(qi, ki, w, *, block: int | None = None,
                 interpret: bool | None = None):
    """As `index_scores_reference` on the pairs s <= t; the tiles wholly
    above the diagonal are left UNWRITTEN (whatever the buffer held), so
    every reader masks by position first. Operands go into the MXU in
    qi's type with float32 accumulation. The arguments are detached: the
    scores are ranked, and what the indexer learns from comes back through
    `index_loss`."""
    qi, ki, w = (jax.lax.stop_gradient(x) for x in (qi, ki, w))
    b, s, heads, d = qi.shape
    blk = _block(s, block)
    if interpret is None:
        interpret = _auto_interpret()
    on_or_under = lambda i, j: jnp.minimum(j, i)  # noqa: E731
    return pl.pallas_call(
        functools.partial(_index_kernel, heads=heads),
        grid=(b, s // blk, s // blk),
        in_specs=[
            pl.BlockSpec((1, heads, blk, d), lambda r, i, j: (r, 0, i, 0)),
            pl.BlockSpec((1, blk, d), lambda r, i, j: (r, on_or_under(i, j), 0)),
            pl.BlockSpec((1, blk, heads), lambda r, i, j: (r, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, blk, blk),
                               lambda r, i, j: (r, i, on_or_under(i, j))),
        out_shape=jax.ShapeDtypeStruct((b, s, s), jnp.float32),
        compiler_params=_params(3), interpret=interpret, name="dsa_index_fwd",
    )(qi.transpose(0, 2, 1, 3), ki.astype(qi.dtype), w.astype(jnp.float32))


# -- the selection ---------------------------------------------------------------------

def _sortable(x):
    """float32 -> int32 that orders as the floats do. -0.0 is counted as
    0.0: a score is a sum that starts at +0.0 and never comes out -0.0, so
    this only spares the order a case (`jax.lax.top_k` on the CPU ranks
    -0.0 under 0.0)."""
    bits = jax.lax.bitcast_convert_type(jnp.where(x == 0, 0.0, x), jnp.int32)
    return jnp.where(bits < 0, bits ^ 0x7FFFFFFF, bits)


def _bisect(count_at_least, bits: int, shape):
    """The largest unsigned `bits`-bit number a (per row) for which
    count_at_least(a) holds, built from the top bit down; it holds at 0."""
    def step(n, a):
        cand = a | jax.lax.shift_left(jnp.int32(1), (bits - 1 - n).astype(jnp.int32))
        return jnp.where(count_at_least(cand), cand, a)

    return jax.lax.fori_loop(0, bits, step, jnp.zeros(shape, jnp.int32))


def _select_rows(scores, q_pos, top_k: int):
    """scores: (..., n, s) of the queries at positions q_pos (n, 1) -> bool,
    True where key s is in S_t. Plain array code: `select` runs it on all
    rows at once, the `dsa_select` kernel on a block of queries in VMEM,
    where the 45 passes over the block cost no memory traffic."""
    s = scores.shape[-1]
    pos = jax.lax.broadcasted_iota(jnp.int32, (1, s), 1)
    # a masked pair is smaller than every candidate threshold (a candidate
    # has a bit set, so, signed, it is above INT_MIN)
    keys = jnp.where(pos <= q_pos, _sortable(scores), _INT_MIN)
    want = jnp.minimum(q_pos + 1, top_k)  # k_t
    shape = scores.shape[:-1] + (1,)

    def count(pred):
        return jnp.sum(pred.astype(jnp.int32), axis=-1, keepdims=True)

    # the k_t-th largest key, as an unsigned number (sign bit flipped)
    tau = _bisect(lambda a: count(keys >= (a ^ _INT_MIN)) >= want, 32, shape) ^ _INT_MIN
    above = keys > tau
    tied = keys == tau
    need = want - count(above)  # of the keys tied AT tau, the first `need`

    def first_tied():
        # the largest c with fewer than `need` tied keys before it: the
        # need-th tied key itself
        return _bisect(lambda c: count(tied & (pos < c)) < need,
                       max(s - 1, 1).bit_length(), shape)

    last = jax.lax.cond(jnp.max(count(tied) - need) > 0, first_tied,
                        lambda: jnp.full(shape, s, jnp.int32))
    return above | (tied & (pos <= last))


def _select_kernel(scores_ref, mask_ref, *, top_k: int):
    n = scores_ref.shape[1]
    q_pos = pl.program_id(1) * n + jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)
    mask_ref[0] = _select_rows(scores_ref[0], q_pos, top_k).astype(jnp.int8)


def select(scores, top_k: int, *, kernel: bool = False,
           interpret: bool | None = None):
    """(mask (b, s, s) int8, 1 where key s is in S_t; the pairs kept, a
    count). Exactly `jax.lax.top_k`'s set: see the module's head. kernel:
    as the Pallas kernel `dsa_select`, a block of queries a program."""
    b, s, _ = scores.shape
    if kernel:
        n = _block(s, _SELECT_ROWS)
        if interpret is None:
            interpret = _auto_interpret()
        mask = pl.pallas_call(
            functools.partial(_select_kernel, top_k=top_k),
            grid=(b, s // n),
            in_specs=[pl.BlockSpec((1, n, s), lambda r, i: (r, i, 0))],
            out_specs=pl.BlockSpec((1, n, s), lambda r, i: (r, i, 0)),
            out_shape=jax.ShapeDtypeStruct((b, s, s), jnp.int8),
            compiler_params=_params(2), interpret=interpret, name="dsa_select",
        )(scores)
    else:
        q_pos = jnp.arange(s, dtype=jnp.int32)[:, None]
        mask = _select_rows(scores, q_pos, top_k).astype(jnp.int8)
    return mask, jnp.sum(mask, dtype=jnp.int32)


# -- attention over the selection --------------------------------------------------

def selected_attention_reference(q, k, v, mask):
    """q: (b, s, h, d); k, v: (b, s, kv, d); mask: (b, s, s) -> (b, s, h, d).
    float32 inside."""
    b, s, h, d = q.shape
    kv = k.shape[2]
    qg = q.reshape(b, s, kv, h // kv, d).astype(jnp.float32)
    prec = (jax.lax.Precision.HIGHEST if q.dtype == jnp.float32 else None)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qg, k.astype(jnp.float32),
                        precision=prec) / math.sqrt(d)
    logits = jnp.where(mask[:, None, None] != 0, logits, NEG_INF)
    o = jnp.einsum("bkgqs,bskd->bqkgd", jax.nn.softmax(logits, axis=-1),
                   v.astype(jnp.float32), precision=prec)
    return o.reshape(b, s, h, d).astype(q.dtype)


def _keep(s, m):
    """Scores of a tile with the pairs outside the selection masked."""
    return jnp.where(m.astype(jnp.int32) != 0, s, NEG_INF)


def _attn_fwd_kernel(q_ref, k_ref, v_ref, m_ref, o_ref, lse_ref, *,
                     block_q: int, block_k: int, scale: float):
    """One (batch*head, q-block) program, as flash_attention's: q (1, bq, d),
    k, v (1, s, d), m (1, bq, s) int8, o (1, bq, d), lse (1, 8, bq). A tile
    in which a query keeps no key leaves m at NEG_INF and p at 1 for it;
    the first tile that holds one of its keys scales that away (alpha = 0),
    and every query has a key."""
    qi = pl.program_id(1)
    q = q_ref[0]
    _, hi = _k_tiles(qi, block_q, block_k, k_ref.shape[1], True, None)

    def body(j, carry):
        acc, m, l = carry
        at = pl.ds(j * block_k, block_k)
        s = _keep(scale * _dot(q, k_ref[0, at, :], ((1,), (1,))), m_ref[0, :, at])
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        vb = v_ref[0, at, :]
        return (acc * alpha + _dot(p.astype(vb.dtype), vb, ((1,), (0,))),
                m_new, alpha * l + jnp.sum(p, axis=1, keepdims=True))

    acc, m, l = jax.lax.fori_loop(
        0, hi, body, (jnp.zeros(o_ref.shape[1:], jnp.float32),
                      jnp.full((block_q, 1), NEG_INF, jnp.float32),
                      jnp.zeros((block_q, 1), jnp.float32)))
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    lse = m[:, 0] + jnp.log(l[:, 0])
    lse_ref[0] = jnp.broadcast_to(lse[None, :], (LSE_SUBLANES, block_q))


def _attn_dq_kernel(q_ref, k_ref, v_ref, m_ref, do_ref, lse_ref, delta_ref,
                    dq_ref, *, block_q: int, block_k: int, scale: float):
    qi = pl.program_id(1)
    q, do = q_ref[0], do_ref[0]
    lse = lse_ref[0, 0, :][:, None]
    delta = delta_ref[0, 0, :][:, None]
    _, hi = _k_tiles(qi, block_q, block_k, k_ref.shape[1], True, None)

    def body(j, dq):
        at = pl.ds(j * block_k, block_k)
        kb, vb = k_ref[0, at, :], v_ref[0, at, :]
        s = _keep(scale * _dot(q, kb, ((1,), (1,))), m_ref[0, :, at])
        p = jnp.exp(s - lse)  # a masked pair underflows to exactly 0
        ds = p * (_dot(do, vb, ((1,), (1,))) - delta) * scale
        return dq + _dot(ds.astype(kb.dtype), kb, ((1,), (0,)))

    dq = jax.lax.fori_loop(0, hi, body, jnp.zeros(dq_ref.shape[1:], jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _attn_dkv_kernel(q_ref, k_ref, v_ref, m_ref, do_ref, lse_ref, delta_ref,
                     dk_ref, dv_ref, dk_acc, dv_acc, *, block_q: int,
                     block_k: int, scale: float, group: int):
    """One (batch*KV-head, k-block, group member) program, as
    flash_attention's dK/dV: q, do (1, s, d) of ONE query head, k, v
    (1, bk, d), m (1, s, bk) int8; float32 scratch carries the sums over the
    group's members."""
    kj, g = pl.program_id(1), pl.program_id(2)
    kb, vb = k_ref[0], v_ref[0]
    lo, hi = _q_tiles(kj, block_q, block_k, q_ref.shape[1], True, None)

    def body(i, carry):
        dk, dv = carry
        at = pl.ds(i * block_q, block_q)
        qb, dob = q_ref[0, at, :], do_ref[0, at, :]
        s = _keep(scale * _dot(qb, kb, ((1,), (1,))), m_ref[0, at, :])
        p = jnp.exp(s - lse_ref[0, 0, at][:, None])
        dv = dv + _dot(p.astype(dob.dtype), dob, ((0,), (0,)))
        ds = p * (_dot(dob, vb, ((1,), (1,))) - delta_ref[0, 0, at][:, None]) * scale
        return dk + _dot(ds.astype(qb.dtype), qb, ((0,), (0,))), dv

    zeros = jnp.zeros(kb.shape, jnp.float32)
    dk, dv = jax.lax.fori_loop(lo, hi, body, (zeros, zeros))

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
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _selected(q, k, v, mask, block, interpret):
    """-> (o, lse): lse (b * h, 8, s) as the kernels keep it, each query's
    log-sum-exp over its selection, for `index_loss`'s kernel; it takes no
    cotangent."""
    return _selected_fwd(q, k, v, mask, block, interpret)[0]


def _selected_fwd(q, k, v, mask, block, interpret):
    b, s, h, d = q.shape
    group = h // k.shape[2]
    qf, kf, vf = _flatten_heads(q), _flatten_heads(k), _flatten_heads(v)
    of, lse = pl.pallas_call(
        functools.partial(_attn_fwd_kernel, block_q=block, block_k=block,
                          scale=1.0 / math.sqrt(d)),
        grid=(b * h, s // block),
        in_specs=[
            pl.BlockSpec((1, block, d), lambda bh, i: (bh, i, 0)),
            pl.BlockSpec((1, s, d), lambda bh, i: (bh // group, 0, 0)),
            pl.BlockSpec((1, s, d), lambda bh, i: (bh // group, 0, 0)),
            pl.BlockSpec((1, block, s), lambda bh, i: (bh // h, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block, d), lambda bh, i: (bh, i, 0)),
            pl.BlockSpec((1, LSE_SUBLANES, block), lambda bh, i: (bh, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, LSE_SUBLANES, s), jnp.float32),
        ],
        compiler_params=_params(2), interpret=interpret, name="dsa_attn_fwd",
    )(qf, kf, vf, mask)
    o = _unflatten_heads(of, b, h)
    return (o, lse), (q, k, v, mask, o, lse)


def _selected_bwd(block, interpret, res, cotangents):
    q, k, v, mask, o, lse = res
    g = cotangents[0]
    b, s, h, d = q.shape
    hk = k.shape[2]
    group = h // hk
    scale = 1.0 / math.sqrt(d)
    qf, kf, vf = _flatten_heads(q), _flatten_heads(k), _flatten_heads(v)
    of, dof = _flatten_heads(o), _flatten_heads(g)
    delta = jnp.sum(dof.astype(jnp.float32) * of.astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta[:, None, :], (b * h, LSE_SUBLANES, s))
    row = lambda bh, i: (bh, 0, i)  # noqa: E731
    dqf = pl.pallas_call(
        functools.partial(_attn_dq_kernel, block_q=block, block_k=block,
                          scale=scale),
        grid=(b * h, s // block),
        in_specs=[
            pl.BlockSpec((1, block, d), lambda bh, i: (bh, i, 0)),
            pl.BlockSpec((1, s, d), lambda bh, i: (bh // group, 0, 0)),
            pl.BlockSpec((1, s, d), lambda bh, i: (bh // group, 0, 0)),
            pl.BlockSpec((1, block, s), lambda bh, i: (bh // h, i, 0)),
            pl.BlockSpec((1, block, d), lambda bh, i: (bh, i, 0)),
            pl.BlockSpec((1, LSE_SUBLANES, block), row),
            pl.BlockSpec((1, LSE_SUBLANES, block), row),
        ],
        out_specs=pl.BlockSpec((1, block, d), lambda bh, i: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
        compiler_params=_params(2), interpret=interpret, name="dsa_attn_dq",
    )(qf, kf, vf, mask, dof, lse, delta)

    head = lambda bkv, j, g: (bkv * group + g, 0, 0)  # noqa: E731
    dkf, dvf = pl.pallas_call(
        functools.partial(_attn_dkv_kernel, block_q=block, block_k=block,
                          scale=scale, group=group),
        grid=(b * hk, s // block, group),
        in_specs=[
            pl.BlockSpec((1, s, d), head),
            pl.BlockSpec((1, block, d), lambda bkv, j, g: (bkv, j, 0)),
            pl.BlockSpec((1, block, d), lambda bkv, j, g: (bkv, j, 0)),
            pl.BlockSpec((1, s, block), lambda bkv, j, g: (bkv // hk, 0, j)),
            pl.BlockSpec((1, s, d), head),
            pl.BlockSpec((1, LSE_SUBLANES, s), head),
            pl.BlockSpec((1, LSE_SUBLANES, s), head),
        ],
        out_specs=[
            pl.BlockSpec((1, block, d), lambda bkv, j, g: (bkv, j, 0)),
            pl.BlockSpec((1, block, d), lambda bkv, j, g: (bkv, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * hk, s, d), k.dtype),
            jax.ShapeDtypeStruct((b * hk, s, d), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((block, d), jnp.float32),
                        pltpu.VMEM((block, d), jnp.float32)],
        compiler_params=_params(3), interpret=interpret, name="dsa_attn_dkv",
    )(qf, kf, vf, mask, dof, lse, delta)
    return (_unflatten_heads(dqf, b, h), _unflatten_heads(dkf, b, hk),
            _unflatten_heads(dvf, b, hk), None)


_selected.defvjp(_selected_fwd, _selected_bwd)


def selected_attention(q, k, v, mask, *, block: int | None = None,
                       interpret: bool | None = None, with_lse: bool = False):
    """softmax(q.k / sqrt(d)) over the keys `mask` keeps, times v. q:
    (b, s, h, d); k, v: (b, s, kv, d) with h % kv == 0; mask: (b, s, s)
    int8, causal (no key after its query) and at least one key a query.
    Differentiable in q, k, v; the mask is a constant. with_lse: also the
    kernels' log-sum-exp a query and head, what `index_loss(lse=)` takes."""
    if q.shape[2] % k.shape[2]:
        raise ValueError(f"q heads {q.shape[2]} not divisible by kv heads {k.shape[2]}")
    if interpret is None:
        interpret = _auto_interpret()
    o, lse = _selected(q, k, v, mask, _block(q.shape[1], block), interpret)
    return (o, jax.lax.stop_gradient(lse)) if with_lse else o


# -- the indexer's loss --------------------------------------------------------------

def _kl_block(q, k, m, scores, qi, ki, w, with_grads: bool):
    """One block of one row's queries. q: (n, h, d); k: (s, kv, d); m, scores:
    (n, s); qi: (n, heads, di); ki: (s, di); w: (n, heads). -> (sum of the
    block's KLs, and with_grads d/dqi, d/dki, d/dw of that sum)."""
    n, h, d = q.shape
    kv = k.shape[1]
    keep = m != 0
    logits = jnp.einsum("qkgd,skd->kgqs", q.reshape(n, kv, h // kv, d), k,
                        preferred_element_type=jnp.float32) / math.sqrt(d)
    probs = jax.nn.softmax(jnp.where(keep, logits, NEG_INF), axis=-1)
    p = jnp.where(keep, jnp.mean(probs, axis=(0, 1)), 0.0)
    log_q = jax.nn.log_softmax(jnp.where(keep, scores, NEG_INF), axis=-1)
    kl = jnp.sum(jnp.where(p > 0, p * (jnp.log(jnp.where(p > 0, p, 1.0)) - log_q), 0.0))
    if not with_grads:
        return kl
    d_scores = jnp.where(keep, jnp.exp(log_q) - p, 0.0)
    dots = jnp.einsum("qjd,sd->jqs", qi, ki, preferred_element_type=jnp.float32)
    dw = jnp.einsum("qs,jqs->qj", d_scores, jax.nn.relu(dots))
    through = (jnp.where(dots > 0, d_scores[None], 0.0)
               * w.astype(jnp.float32).T[:, :, None]).astype(qi.dtype)
    dqi = jnp.einsum("jqs,sd->qjd", through, ki, preferred_element_type=jnp.float32)
    dki = jnp.einsum("jqs,qjd->sd", through, qi, preferred_element_type=jnp.float32)
    return kl, dqi, dki, dw


def _kl_pass(q, k, mask, scores, qi, ki, w, with_grads: bool):
    b, s, h, d = q.shape
    n = min(_KL_BLOCK, s)
    if s % n:
        raise ValueError(f"a sequence of {s} is not tiled by blocks of {n}")
    cut = lambda x: x.reshape(b, s // n, n, *x.shape[2:])  # noqa: E731

    def row(args):
        q_r, k_r, m_r, sc_r, qi_r, ki_r, w_r = args
        out = jax.lax.map(
            lambda blk: _kl_block(blk[0], k_r, blk[1], blk[2], blk[3], ki_r,
                                  blk[4], with_grads),
            (q_r, m_r, sc_r, qi_r, w_r))
        if not with_grads:
            return jnp.sum(out)
        kl, dqi, dki, dw = out
        return jnp.sum(kl), dqi, jnp.sum(dki, axis=0), dw

    out = jax.lax.map(row, (cut(q), k, cut(mask), cut(scores), cut(qi), ki, cut(w)))
    tokens = b * s
    if not with_grads:
        return jnp.sum(out) / tokens
    kl, dqi, dki, dw = out
    return (jnp.sum(kl) / tokens, dqi.reshape(qi.shape) / tokens, dki / tokens,
            dw.reshape(w.shape) / tokens)


# The same loss as three kernels. `dsa_kl_fwd`, one (row, block of queries)
# at a time over the heads: p's (block, seq) slab summed in VMEM from each
# head's exp(q.k / sqrt(d) - lse) on the causal tiles, then the loss a query
# and d loss / d scores = softmax_S(I) - p. `dsa_index_dq` and `dsa_index_dk`
# push that through the score's products, a (512, 512) tile of pairs a step:
# the first sums over a query block's keys (d/dqI, d/dw), the second over a
# key block's queries (d/dkI).

def _kl_kernel(q_ref, k_ref, lse_ref, m_ref, i_ref, kl_ref, di_ref, p_acc, *,
               block_k: int, heads: int, scale: float):
    """One (row, q-block, head) program, the head innermost. q (1, n, d),
    k (1, s, d) of the head's key head, lse (1, 8, n), m (1, n, s) int8, the
    indexer's scores (1, n, s); kl (1, 8, n) and d_scores (1, n, s) are
    written at the last head."""
    qb, head = pl.program_id(1), pl.program_id(2)
    n = q_ref.shape[1]

    @pl.when(head == 0)
    def _zero():
        p_acc[...] = jnp.zeros_like(p_acc)

    q = q_ref[0]
    lse = lse_ref[0, 0, :][:, None]

    def tile(j, _):
        at = pl.ds(j * block_k, block_k)
        s = _keep(scale * _dot(q, k_ref[0, at, :], ((1,), (1,))), m_ref[0, :, at])
        p_acc[:, at] += jnp.exp(s - lse)
        return _

    jax.lax.fori_loop(0, pl.cdiv((qb + 1) * n, block_k), tile, 0)

    @pl.when(head == heads - 1)
    def _loss():
        keep = m_ref[0].astype(jnp.int32) != 0
        p = jnp.where(keep, p_acc[...], 0.0)
        p = p / jnp.sum(p, axis=1, keepdims=True)
        scores = jnp.where(keep, i_ref[0], NEG_INF)
        shifted = scores - jnp.max(scores, axis=1, keepdims=True)
        e = jnp.where(keep, jnp.exp(shifted), 0.0)
        z = jnp.sum(e, axis=1, keepdims=True)
        on = p > 0
        kl = jnp.sum(jnp.where(on, p * (jnp.log(jnp.where(on, p, 1.0))
                                        - (shifted - jnp.log(z))), 0.0), axis=1)
        kl_ref[0] = jnp.broadcast_to(kl[None, :], (LSE_SUBLANES, n))
        di_ref[0] = e / z - p


def _index_dq_kernel(di_ref, q_ref, k_ref, w_ref, dq_ref, dw_ref, dq_acc, dw_acc,
                     *, heads: int):
    """One (row, q-block, k-block) program, the k-block innermost. d_scores
    (1, bq, bk), qI (1, heads, bq, d), kI (1, bk, d), w (1, bq, heads)."""
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _zero():
        dq_acc[...] = jnp.zeros_like(dq_acc)
        dw_acc[...] = jnp.zeros_like(dw_acc)

    @pl.when(j <= i)
    def _tile():
        d_scores, k = di_ref[0], k_ref[0]
        for h in range(heads):
            dots = _dot(q_ref[0, h], k, ((1,), (1,)))
            live = dots > 0
            dw_acc[:, h:h + 1] += jnp.sum(jnp.where(live, dots * d_scores, 0.0),
                                          axis=1, keepdims=True)
            through = jnp.where(live, d_scores * w_ref[0, :, h:h + 1], 0.0)
            dq_acc[h] += _dot(through.astype(k.dtype), k, ((1,), (0,)))

    @pl.when(j == pl.num_programs(2) - 1)
    def _store():
        dq_ref[0] = dq_acc[...]
        dw_ref[0] = dw_acc[...]


def _index_dk_kernel(di_ref, q_ref, k_ref, w_ref, dk_ref, dk_acc, *, heads: int):
    """One (row, k-block, q-block) program, the q-block innermost."""
    j, i = pl.program_id(1), pl.program_id(2)

    @pl.when(i == 0)
    def _zero():
        dk_acc[...] = jnp.zeros_like(dk_acc)

    @pl.when(i >= j)
    def _tile():
        d_scores, k = di_ref[0], k_ref[0]
        for h in range(heads):
            qh = q_ref[0, h]
            dots = _dot(qh, k, ((1,), (1,)))
            through = jnp.where(dots > 0, d_scores * w_ref[0, :, h:h + 1], 0.0)
            dk_acc[...] += _dot(through.astype(qh.dtype), qh, ((0,), (0,)))

    @pl.when(i == pl.num_programs(2) - 1)
    def _store():
        dk_ref[0] = dk_acc[...]


def _kl_kernels(q, k, lse, mask, scores, qi, ki, w, with_grads: bool,
                interpret: bool):
    b, s, h, d = q.shape
    kv = k.shape[2]
    group = h // kv
    heads, di = qi.shape[2], qi.shape[3]
    n, blk = _block(s, _KL_ROWS), _block(s, None)
    slab = lambda r, i, hh: (r, i, 0)  # noqa: E731
    kl, d_scores = pl.pallas_call(
        functools.partial(_kl_kernel, block_k=blk, heads=h, scale=1.0 / math.sqrt(d)),
        grid=(b, s // n, h),
        in_specs=[
            pl.BlockSpec((1, n, d), lambda r, i, hh: (r * h + hh, i, 0)),
            pl.BlockSpec((1, s, d), lambda r, i, hh: (r * kv + hh // group, 0, 0)),
            pl.BlockSpec((1, LSE_SUBLANES, n), lambda r, i, hh: (r * h + hh, 0, i)),
            pl.BlockSpec((1, n, s), slab),
            pl.BlockSpec((1, n, s), slab),
        ],
        out_specs=[pl.BlockSpec((1, LSE_SUBLANES, n), lambda r, i, hh: (r, 0, i)),
                   pl.BlockSpec((1, n, s), slab)],
        out_shape=[jax.ShapeDtypeStruct((b, LSE_SUBLANES, s), jnp.float32),
                   jax.ShapeDtypeStruct((b, s, s), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((n, s), jnp.float32)],
        compiler_params=_params(3, 96), interpret=interpret, name="dsa_kl_fwd",
    )(_flatten_heads(q), _flatten_heads(k), lse, mask, scores)
    tokens = b * s
    loss = jnp.sum(kl[:, 0, :]) / tokens
    if not with_grads:
        return loss
    qit, kit, w = qi.transpose(0, 2, 1, 3), ki.astype(qi.dtype), w.astype(jnp.float32)
    under = lambda i, j: jnp.minimum(j, i)  # noqa: E731  (a tile on or under the diagonal)
    dqi, dw = pl.pallas_call(
        functools.partial(_index_dq_kernel, heads=heads),
        grid=(b, s // blk, s // blk),
        in_specs=[
            pl.BlockSpec((1, blk, blk), lambda r, i, j: (r, i, under(i, j))),
            pl.BlockSpec((1, heads, blk, di), lambda r, i, j: (r, 0, i, 0)),
            pl.BlockSpec((1, blk, di), lambda r, i, j: (r, under(i, j), 0)),
            pl.BlockSpec((1, blk, heads), lambda r, i, j: (r, i, 0)),
        ],
        out_specs=[pl.BlockSpec((1, heads, blk, di), lambda r, i, j: (r, 0, i, 0)),
                   pl.BlockSpec((1, blk, heads), lambda r, i, j: (r, i, 0))],
        out_shape=[jax.ShapeDtypeStruct((b, heads, s, di), jnp.float32),
                   jax.ShapeDtypeStruct((b, s, heads), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((heads, blk, di), jnp.float32),
                        pltpu.VMEM((blk, heads), jnp.float32)],
        compiler_params=_params(3), interpret=interpret, name="dsa_index_dq",
    )(d_scores, qit, kit, w)
    over = lambda j, i: jnp.maximum(i, j)  # noqa: E731
    dki = pl.pallas_call(
        functools.partial(_index_dk_kernel, heads=heads),
        grid=(b, s // blk, s // blk),
        in_specs=[
            pl.BlockSpec((1, blk, blk), lambda r, j, i: (r, over(j, i), j)),
            pl.BlockSpec((1, heads, blk, di), lambda r, j, i: (r, 0, over(j, i), 0)),
            pl.BlockSpec((1, blk, di), lambda r, j, i: (r, j, 0)),
            pl.BlockSpec((1, blk, heads), lambda r, j, i: (r, over(j, i), 0)),
        ],
        out_specs=pl.BlockSpec((1, blk, di), lambda r, j, i: (r, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b, s, di), jnp.float32),
        scratch_shapes=[pltpu.VMEM((blk, di), jnp.float32)],
        compiler_params=_params(3), interpret=interpret, name="dsa_index_dk",
    )(d_scores, qit, kit, w)
    return loss, dqi.transpose(0, 2, 1, 3) / tokens, dki / tokens, dw / tokens


def _loss_pass(q, k, lse, mask, scores, qi, ki, w, with_grads, interpret):
    if lse is None:
        return _kl_pass(q, k, mask, scores, qi, ki, w, with_grads)
    return _kl_kernels(q, k, lse, mask, scores, qi, ki, w, with_grads, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(8,))
def _index_loss(q, k, lse, mask, scores, qi, ki, w, interpret):
    return _loss_pass(q, k, lse, mask, scores, qi, ki, w, False, interpret)


def _index_loss_fwd(q, k, lse, mask, scores, qi, ki, w, interpret):
    loss, dqi, dki, dw = _loss_pass(q, k, lse, mask, scores, qi, ki, w, True, interpret)
    return loss, (dqi.astype(qi.dtype), dki.astype(ki.dtype), dw.astype(w.dtype))


def _index_loss_bwd(interpret, res, g):
    # nothing for q, k, lse, the mask and the scores: constants of this loss
    return (None,) * 5 + tuple((g * d).astype(d.dtype) for d in res)


_index_loss.defvjp(_index_loss_fwd, _index_loss_bwd)


def index_loss(q, k, mask, scores, qi, ki, w, *, lse=None,
               interpret: bool | None = None):
    """mean_t KL(p[t, .] || softmax_{S_t}(I[t, .])). q, k: the main
    attention's, after norm and rotary (they take no gradient from here: p
    is a target, not a path; nor do the scores); mask, scores: `select`'s and `index_scores`'; qi, ki, w: what the
    scores were made from, and the only arguments the gradient reaches. lse:
    `selected_attention(with_lse=True)`'s, and then the kernels run; None:
    plain XLA a block of queries at a time."""
    if interpret is None:
        interpret = _auto_interpret()
    return _index_loss(q, k, lse, mask, scores, qi, ki, w, interpret)
