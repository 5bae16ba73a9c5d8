"""The chunked state-space scan of Mamba-2 (SSD, arXiv:2405.21060), forward
and backward as Pallas kernels.

For each head j of P channels reading group g's B and C (N wide):

    S_t = exp(dt_t a_j) S_{t-1} + dt_t x_t B_t^T       (P x N, S_0 = 0)
    y_t = S_t C_t

The sequence is cut into chunks of Q positions. With cs the cumulative sum
of dt a INSIDE a chunk, H_c the state entering chunk c and u = dt x:

    y_t     = sum_{s <= t in c} (C_t . B_s) exp(cs_t - cs_s) u_s    intra-chunk
            + exp(cs_t) H_c C_t                                    the state
    H_{c+1} = exp(cs_Q) H_c + sum_{s in c} exp(cs_Q - cs_s) u_s B_s^T

`ssd_fwd` runs the chunks of one (row, head) in order on one grid axis,
carrying H in float32 scratch, and writes each chunk's entering H for the
backward. `ssd_bwd` runs them in reverse, carrying dL/dH the same way, and
gives du, dB and dC a head (float32; the heads of a group are summed after)
and dcs. The cumulative sums, dt x, the D skip and the padding to whole
chunks are plain XLA around the kernels; their gradients are autodiff's.
No (seq, seq) tensor exists: a chunk's Q x Q products live in VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpunet.ops.flash_attention import LSE_SUBLANES, NEG_INF, _auto_interpret, _dot_precision

HIGHEST = jax.lax.Precision.HIGHEST
_NT = ((1,), (1,))  # a @ b.T
_NN = ((1,), (0,))  # a @ b
_TN = ((0,), (0,))  # a.T @ b


def _dot(a, b, contract, precision):
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=jnp.float32,
                               precision=precision)


def _decay(cs_col, cs_row):
    """(Q, Q): exp(cs_t - cs_s) where s <= t, else 0."""
    q = cs_col.shape[0]
    t = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    s = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    return jnp.exp(jnp.where(t >= s, cs_col - cs_row, NEG_INF))


def _cs(cs_ref, q):
    """The chunk's cumulative decay as a row (1, Q), a column (Q, 1) and
    its last value (a scalar: Mosaic broadcasts no (1, 1) slice)."""
    row = cs_ref[0, 0:1, :]
    lane = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1)
    return row, cs_ref[0, 0, :][:, None], jnp.sum(jnp.where(lane == q - 1, row, 0.0))


def _fwd_kernel(u_ref, b_ref, c_ref, cs_ref, y_ref, h_ref, state, *, precision):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        state[...] = jnp.zeros(state.shape, jnp.float32)

    u, bm, cm = u_ref[0], b_ref[0], c_ref[0]
    q, dt = u.shape[0], u.dtype
    cs_row, cs_col, last = _cs(cs_ref, q)
    h = state[...]
    h_ref[0, 0] = h
    m = _dot(cm, bm, _NT, precision) * _decay(cs_col, cs_row)
    y = (_dot(m.astype(dt), u, _NN, precision)
         + jnp.exp(cs_col) * _dot(cm, h.astype(dt), _NT, precision))
    y_ref[0] = y.astype(y_ref.dtype)
    w = jnp.exp(last - cs_col)
    state[...] = jnp.exp(last) * h + _dot((u * w).astype(dt), bm, _TN, precision)


def _bwd_kernel(u_ref, b_ref, c_ref, cs_ref, h_ref, dy_ref, du_ref, db_ref,
                dc_ref, dcs_ref, grad, *, precision):
    """One chunk, the last first; `grad` carries dL/dH of the state that
    LEAVES the chunk."""
    @pl.when(pl.program_id(1) == 0)
    def _init():
        grad[...] = jnp.zeros(grad.shape, jnp.float32)

    u, bm, cm, dy = u_ref[0], b_ref[0], c_ref[0], dy_ref[0]
    q, p, dt = u.shape[0], u.shape[1], u.dtype
    cs_row, cs_col, last = _cs(cs_ref, q)
    h, g = h_ref[0, 0], grad[...]
    decay = _decay(cs_col, cs_row)
    scores = _dot(cm, bm, _NT, precision)
    # intra-chunk: y = (scores * decay) u
    du = _dot((scores * decay).astype(dt), dy, _TN, precision)
    dcb = _dot(dy, u, _NT, precision) * decay
    dc = _dot(dcb.astype(dt), bm, _NN, precision)
    db = _dot(dcb.astype(dt), cm, _TN, precision)
    r = dcb * scores
    # the entering state's part: exp(cs_t) H C_t
    e_col = jnp.exp(cs_col)
    dc = dc + e_col * _dot(dy, h.astype(dt), _NN, precision)
    y_off = e_col * _dot(cm, h.astype(dt), _NT, precision)
    # the leaving state: exp(cs_Q) H + sum_s w_s u_s B_s^T
    w_col = jnp.exp(last - cs_col)
    bg = _dot(bm, g.astype(dt), _NT, precision)
    du = du + w_col * bg
    db = db + w_col * _dot(u, g.astype(dt), _NN, precision)
    # dcs as a row: sums over a row of (Q, .) are products with ones
    ones_q = jnp.ones((LSE_SUBLANES, q), jnp.float32)
    ones_p = jnp.ones((LSE_SUBLANES, p), jnp.float32)
    dw = _dot(ones_p, u.astype(jnp.float32) * bg, _NT, HIGHEST)
    w_row = jnp.exp(last - cs_row)
    dcs = (_dot(ones_q, r, _NT, HIGHEST) - _dot(ones_q, r, _NN, HIGHEST)
           + _dot(ones_p, dy.astype(jnp.float32) * y_off, _NT, HIGHEST) - dw * w_row)
    to_last = jnp.sum(dw * w_row, axis=1, keepdims=True) + jnp.exp(last) * jnp.sum(g * h)
    lane = jax.lax.broadcasted_iota(jnp.int32, dcs.shape, 1)
    dcs = dcs + jnp.where(lane == q - 1, to_last, 0.0)
    du_ref[0] = du.astype(du_ref.dtype)
    db_ref[0] = db
    dc_ref[0] = dc
    dcs_ref[0] = dcs
    grad[...] = jnp.exp(last) * g + _dot(
        (dy.astype(jnp.float32) * e_col).astype(dt), cm, _TN, precision)


# -- the calls -----------------------------------------------------------------

class _Shapes:
    """Block specs of one (rows * heads, seq, .) problem whose B and C are
    (rows * groups, seq, N); `rev` runs the chunks last first."""

    def __init__(self, bh, seq, p, n, per_group, chunk, rev):
        self.bh, self.seq, self.p, self.n, self.chunk = bh, seq, p, n, chunk
        self.nc = seq // chunk
        at = (lambda c: self.nc - 1 - c) if rev else (lambda c: c)
        self.rows_p = pl.BlockSpec((1, chunk, p), lambda i, c: (i, at(c), 0))
        self.rows_n = pl.BlockSpec((1, chunk, n), lambda i, c: (i, at(c), 0))
        self.group = pl.BlockSpec((1, chunk, n), lambda i, c: (i // per_group, at(c), 0))
        self.lanes = pl.BlockSpec((1, LSE_SUBLANES, chunk), lambda i, c: (i, 0, at(c)))
        self.state = pl.BlockSpec((1, 1, p, n), lambda i, c: (i, at(c), 0, 0))

    def out(self, width, dtype):
        return jax.ShapeDtypeStruct((self.bh, self.seq, width), dtype)


def _call(kernel, name, sh, in_specs, out_specs, out_shape, interpret, precision):
    return pl.pallas_call(
        functools.partial(kernel, precision=precision), grid=(sh.bh, sh.nc),
        in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((sh.p, sh.n), jnp.float32)], interpret=interpret,
        name=name, compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")))


def _lanes(cs):
    return jnp.broadcast_to(cs[:, None, :], (cs.shape[0], LSE_SUBLANES, cs.shape[1]))


def _forward(u, cs, b, c, chunk, interpret):
    bh, seq, p = u.shape
    n = b.shape[2]
    sh = _Shapes(bh, seq, p, n, bh // b.shape[0], chunk, rev=False)
    return _call(
        _fwd_kernel, "ssd_fwd", sh, [sh.rows_p, sh.group, sh.group, sh.lanes],
        [sh.rows_p, sh.state],
        [sh.out(p, u.dtype), jax.ShapeDtypeStruct((bh, sh.nc, p, n), jnp.float32)],
        interpret, _dot_precision(u.dtype))(u, b, c, _lanes(cs))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _scan(u, cs, b, c, chunk, interpret):
    return _forward(u, cs, b, c, chunk, interpret)[0]


def _scan_fwd(u, cs, b, c, chunk, interpret):
    y, states = _forward(u, cs, b, c, chunk, interpret)
    return y, (u, cs, b, c, states)


def _scan_bwd(chunk, interpret, res, dy):
    u, cs, b, c, states = res
    bh, seq, p = u.shape
    n = b.shape[2]
    per_group = bh // b.shape[0]
    sh = _Shapes(bh, seq, p, n, per_group, chunk, rev=True)
    du, db, dc, dcs = _call(
        _bwd_kernel, "ssd_bwd", sh,
        [sh.rows_p, sh.group, sh.group, sh.lanes, sh.state, sh.rows_p],
        [sh.rows_p, sh.rows_n, sh.rows_n, sh.lanes],
        [sh.out(p, u.dtype), sh.out(n, jnp.float32), sh.out(n, jnp.float32),
         jax.ShapeDtypeStruct((bh, LSE_SUBLANES, seq), jnp.float32)],
        interpret, _dot_precision(u.dtype))(u, b, c, _lanes(cs), states, dy.astype(u.dtype))
    per_head = lambda x, like: x.reshape(  # noqa: E731
        like.shape[0], per_group, seq, n).sum(1).astype(like.dtype)
    return du, dcs[:, 0, :], per_head(db, b), per_head(dc, c)


_scan.defvjp(_scan_fwd, _scan_bwd)


def ssd_scan(x, dt, a, b, c, chunk: int, interpret: bool | None = None):
    """x: (rows, seq, heads, P); dt: (rows, seq, heads), the step (after
    its softplus); a: (heads,), negative; b, c: (rows, seq, groups, N), head
    j reading group j * groups // heads. Returns y (rows, seq, heads, P) in
    x's dtype, without the D skip. Products take x, B and C in their own
    dtype and accumulate in float32; the decays and the carried state are
    float32. A sequence that is not a whole number of chunks is padded
    with zeros at its end, which changes nothing before it."""
    if interpret is None:
        interpret = _auto_interpret()
    rows, seq, heads, p = x.shape
    groups, n = b.shape[2], b.shape[3]
    if heads % groups:
        raise ValueError(f"{heads} heads do not split into {groups} groups")
    if not interpret and chunk % 128:
        raise ValueError(f"the compiled scan needs a chunk that is a multiple "
                         f"of 128, not {chunk}")
    dt = dt.astype(jnp.float32)
    u = (dt[..., None] * x.astype(jnp.float32)).astype(x.dtype)
    la = dt * a.astype(jnp.float32)
    pad = -seq % chunk
    if pad:
        tail = lambda t: jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))  # noqa: E731
        u, la, b, c = tail(u), tail(la), tail(b), tail(c)
    length = seq + pad
    cs = jnp.cumsum(la.reshape(rows, length // chunk, chunk, heads), axis=2)
    flat = lambda t: jnp.swapaxes(t, 1, 2).reshape(-1, length, t.shape[-1])  # noqa: E731
    y = _scan(flat(u), cs.reshape(rows, length, heads).transpose(0, 2, 1).reshape(-1, length),
              flat(b.astype(x.dtype)), flat(c.astype(x.dtype)), chunk, interpret)
    return jnp.swapaxes(y.reshape(rows, heads, length, p), 1, 2)[:, :seq]
