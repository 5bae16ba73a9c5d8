"""EVA chunk-summary attention (arXiv:2302.04542, the chunked deterministic
form): exact causal attention inside each window of `window` positions, and
attention to one learned summary per `chunk` positions of every EARLIER
window, both under ONE softmax.

    a_m    = phi_h . k_m                       (per head h; m in chunk c)
    pi     = softmax of a over the chunk's positions
    khat_c = sum_m pi_m k_m + mu_h,   vhat_c = sum_m pi_m v_m
    o_t    = softmax over [k_m : m <= t, same window] ++ [khat_c : c's window
             earlier than t's] of q_t . key / sqrt(d), applied to [v_m] ++ [vhat_c]

A query sees at most `window` keys and seq/chunk summaries, so the cost grows
as seq * (window/2 + seq/(2*chunk)) where causal attention grows as seq^2/2.

On the device the two key sets are two kernel families, named `eva_local_*`
and `eva_remote_*` in the program's HLO: each forward kernel returns its
partial (o, lse), merged as ring attention merges blocks (`eva.merge`); the
backward hands both families the MERGED lse and delta = rowsum(do * o), so
each recomputes its share of the one softmax. No (seq, seq) and no
(seq, seq/chunk) score tensor ever exists in HBM. The summaries and their
backward are plain XLA under `eva.summarize` (chunk x head_dim a chunk: bound
by bytes). A q-block never straddles a window, so the remote kernels need no
mask: the k-loop's upper bound is the number of earlier windows, and the
first window, which has none, gets weight 0 (lse = NEG_INF), never 0/0.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpunet.ops.flash_attention import (
    LSE_SUBLANES, NEG_INF, _auto_interpret, _dot_precision, _flatten_heads,
    _unflatten_heads)


def summarize(k, v, phi, mu, chunk: int):
    """Chunk summaries. k, v: (b, s, h, d) (k after the rotary); phi, mu:
    (h, d). Returns khat, vhat: (b, s // chunk, h, d) in k's dtype, computed
    in float32."""
    b, s, h, d = k.shape
    if s % chunk:
        raise ValueError(f"seq {s} is not a multiple of the chunk {chunk}")
    with jax.named_scope("eva.summarize"):
        kc = k.reshape(b, s // chunk, chunk, h, d).astype(jnp.float32)
        vc = v.reshape(b, s // chunk, chunk, h, d).astype(jnp.float32)
        a = jnp.sum(kc * phi.astype(jnp.float32), axis=-1, keepdims=True)
        pi = jax.nn.softmax(a, axis=2)
        khat = jnp.sum(pi * kc, axis=2) + mu.astype(jnp.float32)
        vhat = jnp.sum(pi * vc, axis=2)
        return khat.astype(k.dtype), vhat.astype(v.dtype)


def _dense_attention(q, k, v, khat, vhat, window: int, chunk: int):
    """The equations with a dense mask over (s, s + s/chunk) scores."""
    dt = q.dtype
    s, d = q.shape[1], q.shape[-1]
    prec = _dot_precision(dt)
    keys = jnp.concatenate([k, khat], axis=1).astype(jnp.float32)
    vals = jnp.concatenate([v, vhat], axis=1).astype(jnp.float32)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), keys,
                        precision=prec) / math.sqrt(d)
    t = jnp.arange(s)[:, None]
    m = jnp.arange(s)[None, :]
    local = (m <= t) & (m // window == t // window)
    c = jnp.arange(khat.shape[1])[None, :]
    remote = c < (window // chunk) * (t // window)
    keep = jnp.concatenate([local, remote], axis=1)
    p = jax.nn.softmax(jnp.where(keep, scores, NEG_INF), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, vals, precision=prec).astype(dt)


def eva_attention_reference(q, k, v, phi, mu, window: int, chunk: int):
    """Plain jnp EVA attention, float32 inside. Shapes as `eva_attention`."""
    if window % chunk:
        raise ValueError(f"window {window} is not a multiple of chunk {chunk}")
    khat, vhat = summarize(k, v, phi, mu, chunk)
    return _dense_attention(q, k, v, khat, vhat, window, chunk)


# -- kernels -------------------------------------------------------------------
# Refs hold one head: q/o/do blocks (1, block_q, d); a window of k/v
# (1, window, d) for the local family; all summaries (1, s/chunk, d) for the
# remote one; lse/delta (1, LSE_SUBLANES, block) as flash_attention lays
# them out. Products take the operands in their own dtype (bf16 feeds the
# MXU directly) and accumulate in float32.

def _dot(a, b, contract, precision):
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=jnp.float32,
                               precision=precision)


_NT = ((1,), (1,))  # a @ b.T
_NN = ((1,), (0,))  # a @ b
_TN = ((0,), (0,))  # a.T @ b


def _rows(ref, start, size):
    return ref[0, pl.ds(pl.multiple_of(start, size), size), :]


def _kv_blocks(k_ref, v_ref, block):
    """j -> (k, v) rows [j * block, (j + 1) * block) of the staged keys."""
    return lambda j: (_rows(k_ref, j * block, block), _rows(v_ref, j * block, block))


def _lane_col(ref, start, size):
    """A block of a sublane-replicated row vector as a (size, 1) column."""
    return ref[0, 0, pl.ds(pl.multiple_of(start, size), size)][:, None]


def _local_mask(s, q0, k0):
    """Causal mask on positions inside one window."""
    qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(qpos >= kpos, s, NEG_INF)


def _online_softmax(q, n_blocks, block, scale, precision, kv_block, mask=None):
    """(o, lse) of q over blocks 0..n_blocks-1 of keys; no block: (0, NEG_INF)."""
    def body(j, carry):
        acc, m, l = carry
        kb, vb = kv_block(j)
        s = _dot(q, kb, _NT, precision) * scale
        if mask is not None:
            s = mask(s, j * block)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
        acc = acc * alpha + _dot(p.astype(vb.dtype), vb, _NN, precision)
        return acc, m_new, l

    bq, d = q.shape
    acc, m, l = jax.lax.fori_loop(
        0, n_blocks, body,
        (jnp.zeros((bq, d), jnp.float32), jnp.full((bq, 1), NEG_INF, jnp.float32),
         jnp.zeros((bq, 1), jnp.float32)))
    l = jnp.where(l == 0.0, 1.0, l)
    return acc / l, m[:, 0] + jnp.log(l[:, 0])


def _write_o_lse(o_ref, lse_ref, o, lse):
    o_ref[0, :, :] = o.astype(o_ref.dtype)
    lse_ref[0, :, :] = jnp.broadcast_to(lse[None, :], lse_ref.shape[1:])


def _local_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_q, block_k,
                      window, scale, precision):
    q0 = (pl.program_id(1) * block_q) % window  # first row, inside its window
    o, lse = _online_softmax(
        q_ref[0], pl.cdiv(q0 + block_q, block_k), block_k, scale, precision,
        _kv_blocks(k_ref, v_ref, block_k),
        lambda s, k0: _local_mask(s, q0, k0))
    _write_o_lse(o_ref, lse_ref, o, lse)


def _remote_fwd_kernel(q_ref, kh_ref, vh_ref, o_ref, lse_ref, *, block_q,
                       per_window, window, scale, precision):
    earlier = (pl.program_id(1) * block_q) // window  # windows before this one
    o, lse = _online_softmax(
        q_ref[0], earlier, per_window, scale, precision,
        _kv_blocks(kh_ref, vh_ref, per_window))
    _write_o_lse(o_ref, lse_ref, o, lse)


def _dq_blocks(q, do, lse, delta, n_blocks, block, scale, precision, kv_block,
               mask=None):
    def body(j, dq):
        kb, vb = kv_block(j)
        s = _dot(q, kb, _NT, precision) * scale
        if mask is not None:
            s = mask(s, j * block)
        p = jnp.exp(s - lse)  # masked entries underflow to exactly 0
        ds = p * (_dot(do, vb, _NT, precision) - delta) * scale
        return dq + _dot(ds.astype(kb.dtype), kb, _NN, precision)

    return jax.lax.fori_loop(0, n_blocks, body, jnp.zeros(q.shape, jnp.float32))


def _local_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *,
                     block_q, block_k, window, scale, precision):
    q0 = (pl.program_id(1) * block_q) % window
    dq = _dq_blocks(
        q_ref[0], do_ref[0], lse_ref[0, 0, :][:, None], delta_ref[0, 0, :][:, None],
        pl.cdiv(q0 + block_q, block_k), block_k, scale, precision,
        _kv_blocks(k_ref, v_ref, block_k),
        lambda s, k0: _local_mask(s, q0, k0))
    dq_ref[0, :, :] = dq.astype(dq_ref.dtype)


def _remote_dq_kernel(q_ref, kh_ref, vh_ref, do_ref, lse_ref, delta_ref, dq_ref,
                      *, block_q, per_window, window, scale, precision):
    earlier = (pl.program_id(1) * block_q) // window
    dq = _dq_blocks(
        q_ref[0], do_ref[0], lse_ref[0, 0, :][:, None], delta_ref[0, 0, :][:, None],
        earlier, per_window, scale, precision,
        _kv_blocks(kh_ref, vh_ref, per_window))
    dq_ref[0, :, :] = dq.astype(dq_ref.dtype)


def _dkv_block(qb, dob, lse, delta, kb, vb, scale, precision, mask=None):
    """One q-block's share of (dk, dv) of one key block."""
    s = _dot(qb, kb, _NT, precision) * scale
    if mask is not None:
        s = mask(s)
    p = jnp.exp(s - lse)
    dv = _dot(p.astype(dob.dtype), dob, _TN, precision)
    ds = p * (_dot(dob, vb, _NT, precision) - delta) * scale
    return _dot(ds.astype(qb.dtype), qb, _TN, precision), dv


def _local_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
                      dv_ref, *, block_q, block_k, window, scale, precision):
    """One k-block; q/do/lse/delta refs hold that block's whole window."""
    k0 = (pl.program_id(1) * block_k) % window
    kb, vb = k_ref[0], v_ref[0]

    def body(i, carry):
        dk, dv = carry
        q0 = i * block_q
        ddk, ddv = _dkv_block(
            _rows(q_ref, q0, block_q), _rows(do_ref, q0, block_q),
            _lane_col(lse_ref, q0, block_q), _lane_col(delta_ref, q0, block_q),
            kb, vb, scale, precision, lambda s: _local_mask(s, q0, k0))
        return dk + ddk, dv + ddv

    zeros = jnp.zeros(kb.shape, jnp.float32)
    dk, dv = jax.lax.fori_loop(k0 // block_q, window // block_q, body,
                               (zeros, zeros))
    dk_ref[0, :, :] = dk.astype(dk_ref.dtype)
    dv_ref[0, :, :] = dv.astype(dv_ref.dtype)


def _remote_dkv_kernel(q_ref, kh_ref, vh_ref, do_ref, lse_ref, delta_ref,
                       dkh_ref, dvh_ref, dk_acc, dv_acc, *, first_q_block,
                       scale, precision):
    """One window's summaries (grid axis 1) against one q-block (axis 2, the
    reduction): only the q-blocks of LATER windows see them. The partial
    sums live in float32 scratch and are written once, at the last q-block."""
    w, i = pl.program_id(1), pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        dk_acc[...] = jnp.zeros(dk_acc.shape, jnp.float32)
        dv_acc[...] = jnp.zeros(dv_acc.shape, jnp.float32)

    @pl.when(i >= first_q_block(w))
    def _accumulate():
        dk, dv = _dkv_block(
            q_ref[0], do_ref[0], lse_ref[0, 0, :][:, None],
            delta_ref[0, 0, :][:, None], kh_ref[0], vh_ref[0], scale, precision)
        dk_acc[...] += dk
        dv_acc[...] += dv

    @pl.when(i == pl.num_programs(2) - 1)
    def _flush():
        dkh_ref[0, :, :] = dk_acc[...].astype(dkh_ref.dtype)
        dvh_ref[0, :, :] = dv_acc[...].astype(dvh_ref.dtype)


# -- the calls -----------------------------------------------------------------

def _plan(s: int, window: int, chunk: int, block_q: int, block_k: int,
          interpret: bool, dtype):
    """(block_q, block_k) of the kernel path, or None where the shapes do not
    tile (then the dense equations run). Compiled kernels put block_q on the
    lane dim of lse (a multiple of 128) and one window's summaries on a
    sublane dim (a multiple of the dtype's tile)."""
    if s % window or window % chunk:
        return None
    block_q, block_k = min(block_q, window), min(block_k, window)
    if window % block_q or window % block_k:
        return None
    if not interpret:
        sublane = 32 // jnp.dtype(dtype).itemsize
        if block_q % 128 or block_k % sublane or (window // chunk) % sublane:
            return None
    return block_q, block_k


def _call(kernel, name, grid, in_specs, out_specs, out_shape, interpret,
          scratch_shapes=(), reduction_axis=False):
    semantics = ("parallel",) * (len(grid) - reduction_axis) + (
        ("arbitrary",) if reduction_axis else ())
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, scratch_shapes=scratch_shapes, interpret=interpret,
        name=name,
        compiler_params=pltpu.CompilerParams(dimension_semantics=semantics))


class _Shapes:
    """Block specs of one (batch*heads, seq, d) problem."""

    def __init__(self, bh, s, d, window, chunk, block_q, block_k):
        self.bh, self.s, self.d = bh, s, d
        self.window, self.per_window = window, window // chunk
        self.block_q, self.block_k = block_q, block_k
        per_q = window // block_q
        self.q_block = pl.BlockSpec((1, block_q, d), lambda h, i: (h, i, 0))
        self.q_lane = pl.BlockSpec((1, LSE_SUBLANES, block_q), lambda h, i: (h, 0, i))
        self.k_block = pl.BlockSpec((1, block_k, d), lambda h, j: (h, j, 0))
        # the window a q-block (or a k-block) lies in, whole
        self.win_of_q = pl.BlockSpec((1, window, d), lambda h, i: (h, i // per_q, 0))
        per_k = window // block_k
        self.win_of_k = pl.BlockSpec((1, window, d), lambda h, j: (h, j // per_k, 0))
        self.win_lane_of_k = pl.BlockSpec(
            (1, LSE_SUBLANES, window), lambda h, j: (h, 0, j // per_k))
        self.summaries = pl.BlockSpec((1, s // chunk, d), lambda h, i: (h, 0, 0))

    def rows(self, dtype):
        return jax.ShapeDtypeStruct((self.bh, self.s, self.d), dtype)

    def lanes(self):
        return jax.ShapeDtypeStruct((self.bh, LSE_SUBLANES, self.s), jnp.float32)


def _forward(qf, kf, vf, khf, vhf, sh: _Shapes, interpret):
    """Merged (o in q's dtype, lse) of the flattened problem."""
    scale, prec = 1.0 / math.sqrt(sh.d), _dot_precision(qf.dtype)
    grid = (sh.bh, sh.s // sh.block_q)
    partial = ([sh.q_block, sh.q_lane], [sh.rows(jnp.float32), sh.lanes()])
    o_l, lse_l = _call(
        functools.partial(_local_fwd_kernel, block_q=sh.block_q, block_k=sh.block_k,
                          window=sh.window, scale=scale, precision=prec),
        "eva_local_fwd", grid, [sh.q_block, sh.win_of_q, sh.win_of_q], *partial,
        interpret)(qf, kf, vf)
    o_r, lse_r = _call(
        functools.partial(_remote_fwd_kernel, block_q=sh.block_q,
                          per_window=sh.per_window, window=sh.window, scale=scale,
                          precision=prec),
        "eva_remote_fwd", grid, [sh.q_block, sh.summaries, sh.summaries], *partial,
        interpret)(qf, khf, vhf)
    with jax.named_scope("eva.merge"):
        lse = jnp.logaddexp(lse_l, lse_r)
        w_l = jnp.exp(lse_l - lse)[:, 0, :, None]
        w_r = jnp.exp(lse_r - lse)[:, 0, :, None]
        return (o_l * w_l + o_r * w_r).astype(qf.dtype), lse


def _backward(qf, kf, vf, khf, vhf, of, lse, dof, sh: _Shapes, interpret):
    scale, prec = 1.0 / math.sqrt(sh.d), _dot_precision(qf.dtype)
    delta = jnp.sum(dof.astype(jnp.float32) * of.astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta[:, None, :], lse.shape)
    n_q = sh.s // sh.block_q
    grid = (sh.bh, n_q)
    row_in = [sh.q_block, sh.q_lane, sh.q_lane]  # do, lse, delta
    dq_l = _call(
        functools.partial(_local_dq_kernel, block_q=sh.block_q, block_k=sh.block_k,
                          window=sh.window, scale=scale, precision=prec),
        "eva_local_dq", grid, [sh.q_block, sh.win_of_q, sh.win_of_q] + row_in,
        sh.q_block, sh.rows(jnp.float32), interpret)(qf, kf, vf, dof, lse, delta)
    dq_r = _call(
        functools.partial(_remote_dq_kernel, block_q=sh.block_q,
                          per_window=sh.per_window, window=sh.window, scale=scale,
                          precision=prec),
        "eva_remote_dq", grid, [sh.q_block, sh.summaries, sh.summaries] + row_in,
        sh.q_block, sh.rows(jnp.float32), interpret)(qf, khf, vhf, dof, lse, delta)
    with jax.named_scope("eva.merge"):
        dq = (dq_l + dq_r).astype(qf.dtype)
    dk, dv = _call(
        functools.partial(_local_dkv_kernel, block_q=sh.block_q, block_k=sh.block_k,
                          window=sh.window, scale=scale, precision=prec),
        "eva_local_dkv", (sh.bh, sh.s // sh.block_k),
        [sh.win_of_k, sh.k_block, sh.k_block, sh.win_of_k, sh.win_lane_of_k,
         sh.win_lane_of_k],
        [sh.k_block, sh.k_block], [sh.rows(kf.dtype), sh.rows(vf.dtype)],
        interpret)(qf, kf, vf, dof, lse, delta)

    # summaries of window w are seen from q-block (w + 1) * per_q on; the
    # steps before it fetch that block, once, and compute nothing
    per_q = sh.window // sh.block_q
    first = lambda w: (w + 1) * per_q  # noqa: E731
    q_from = lambda h, w, i: (h, jnp.minimum(jnp.maximum(i, first(w)), n_q - 1), 0)  # noqa: E731
    lane_from = lambda h, w, i: (h, 0, q_from(h, w, i)[1])  # noqa: E731
    q3 = pl.BlockSpec((1, sh.block_q, sh.d), q_from)
    lane3 = pl.BlockSpec((1, LSE_SUBLANES, sh.block_q), lane_from)
    summ3 = pl.BlockSpec((1, sh.per_window, sh.d), lambda h, w, i: (h, w, 0))
    n_s = khf.shape[1]
    dkh, dvh = _call(
        functools.partial(_remote_dkv_kernel, first_q_block=first, scale=scale,
                          precision=prec),
        "eva_remote_dkv", (sh.bh, sh.s // sh.window, n_q),
        [q3, summ3, summ3, q3, lane3, lane3], [summ3, summ3],
        [jax.ShapeDtypeStruct((sh.bh, n_s, sh.d), khf.dtype),
         jax.ShapeDtypeStruct((sh.bh, n_s, sh.d), vhf.dtype)],
        interpret, reduction_axis=True,
        scratch_shapes=[pltpu.VMEM((sh.per_window, sh.d), jnp.float32),
                        pltpu.VMEM((sh.per_window, sh.d), jnp.float32)],
    )(qf, khf, vhf, dof, lse, delta)
    return dq, dk, dv, dkh, dvh


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _eva_core(q, k, v, khat, vhat, window, chunk, block_q, block_k, interpret):
    return _core_fwd(q, k, v, khat, vhat, window, chunk, block_q, block_k,
                     interpret)[0]


def _core_fwd(q, k, v, khat, vhat, window, chunk, block_q, block_k, interpret):
    b, s, h, d = q.shape
    sh = _Shapes(b * h, s, d, window, chunk, block_q, block_k)
    flat = tuple(_flatten_heads(x) for x in (q, k, v, khat, vhat))
    of, lse = _forward(*flat, sh, interpret)
    return _unflatten_heads(of, b, h), (flat, of, lse)


def _core_bwd(window, chunk, block_q, block_k, interpret, res, g):
    flat, of, lse = res
    b, s, h, d = g.shape
    sh = _Shapes(b * h, s, d, window, chunk, block_q, block_k)
    grads = _backward(*flat, of, lse, _flatten_heads(g), sh, interpret)
    return tuple(_unflatten_heads(x, b, h) for x in grads)


_eva_core.defvjp(_core_fwd, _core_bwd)


def eva_attention(q, k, v, phi, mu, window: int, chunk: int, block_q: int = 512,
                  block_k: int = 512, interpret: bool | None = None):
    """EVA attention. q, k, v: (batch, seq, heads, head_dim), q and k after
    the rotary; phi, mu: (heads, head_dim), the learned per-head vectors of
    the chunk summaries. Returns q-shaped output.

    Shapes that do not tile (seq not a multiple of the window, a window no
    block divides, on the chip a block off the lane tiling) run the dense
    equations instead, as flash_attention does for ragged shapes."""
    if q.shape != k.shape or k.shape != v.shape:
        raise ValueError("eva_attention takes q, k, v of one shape (no GQA)")
    if window % chunk:
        raise ValueError(f"window {window} is not a multiple of chunk {chunk}")
    if interpret is None:
        interpret = _auto_interpret()
    khat, vhat = summarize(k, v, phi, mu, chunk)
    plan = _plan(q.shape[1], window, chunk, block_q, block_k, interpret, q.dtype)
    if plan is None:
        return _dense_attention(q, k, v, khat, vhat, window, chunk)
    return _eva_core(q, k, v, khat, vhat, window, chunk, *plan, interpret)
