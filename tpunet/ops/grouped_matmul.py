"""Grouped matrix product — Pallas TPU kernels, forward and backward.

What an expert layer's three products need and no dense kernel gives: the
rows of a buffer fall into groups of unequal, data-dependent size, and the
rows of group g are multiplied with matrix g of a stack. The reference
(bagua-net) is a transport and has no kernels.

The layout (TPU-first: no mask inside a tile, no tile visited twice):

  * the row buffer is cut into tiles of `tile_m` rows and a group starts on
    a tile boundary, so a tile belongs to ONE group. Group g owns
    max(1, ceil(size_g / tile_m)) tiles; the rows of its last tile past
    size_g are padding and must be ZERO in every operand (`group_tiles`
    says where each group starts; the caller places the rows).
  * the buffer's static size is the worst case, `buffer_rows(max_rows,
    groups, tile_m)`; the tiles actually owned are a traced count. A tile
    past that count costs no product and no read: its index map points at
    the last live tile (no new DMA) and the kernel writes zeros.
  * two scalar-prefetch operands steer the index maps: `tile_group[i]`,
    the group of tile i, and `n_tiles`, the live count.

Three kernels, each under its own name in the HLO (`pallas_call(name=)`),
which is how the benchmark's readers find them on the device's timeline:

  * `moe_gmm_fwd`:  out[rows of g] = x[rows of g] @ w[g]
  * `moe_gmm_dx`:   the same kernel against the transposed matrices,
                    dx[rows of g] = dout[rows of g] @ w[g]^T
  * `moe_tgmm_dw`:  dw[g] = x[rows of g]^T @ dout[rows of g], accumulated
                    over the tiles of a group in VMEM, float32.

Operands go into the MXU in the rows' type (bf16 in the model) with float32
accumulation; the matrices are cast to it here, so their gradient comes
back in THEIR type (float32 masters) straight from the f32 accumulator.
The (tile_m, tile_k, tile_n) tile is chosen from the call's shapes by
`_plan`; there is no option for it above this module.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpunet.ops.flash_attention import _auto_interpret

_TILE_M = 512
_VMEM_LIMIT = 48 * 1024 * 1024   # of a v5e core's 128 MiB
_VMEM_BLOCKS = 36 * 1024 * 1024  # what a plan's blocks may take of it


def tile_rows(max_rows: int, dtype) -> int:
    """Rows a tile, from the most rows the buffer may have to hold: 512 at
    the sizes a model runs, the whole (sublane-rounded) buffer when it is
    smaller than that."""
    sublane = 32 // jnp.dtype(dtype).itemsize
    return min(_TILE_M, -(-max(max_rows, 1) // sublane) * sublane)


def buffer_rows(max_rows: int, groups: int, tile_m: int) -> int:
    """The static size of the row buffer: sum_g max(1, ceil(size_g / tile_m))
    <= floor(max_rows / tile_m) + groups whatever the sizes."""
    return tile_m * (max_rows // tile_m + groups)


def group_tiles(group_sizes, tile_m: int, rows: int):
    """(starts, tile_group, n_tiles) of a buffer of `rows` rows: the row at
    which each group starts, the group of each tile, the live tiles."""
    tiles = jnp.maximum(1, -(-group_sizes // tile_m)).astype(jnp.int32)
    ends = jnp.cumsum(tiles)
    tile_group = jnp.searchsorted(ends, jnp.arange(rows // tile_m, dtype=jnp.int32),
                                  side="right").astype(jnp.int32)
    return (ends - tiles) * tile_m, tile_group, ends[-1:]


def _divisor_tile(dim: int, most: int) -> int:
    """The whole dimension if it fits `most`, else its largest divisor that
    is a multiple of 128 (a lane tile) and no larger."""
    if dim <= most:
        return dim
    for t in range(most - most % 128, 0, -128):
        if dim % t == 0:
            return t
    return dim


def _fit(blocks, k: int, n: int):
    """(tile_k, tile_n): the whole (k, n) where `blocks(tile_k, tile_n)`
    bytes fit the plan's share of VMEM, else the larger of the two halved
    (to a divisor that is a whole number of lane tiles) until they do."""
    tk, tn = k, n
    while blocks(tk, tn) > _VMEM_BLOCKS:
        half_k, half_n = _divisor_tile(tk, tk // 2), _divisor_tile(tn, tn // 2)
        if half_n < tn and (tn >= tk or half_k == tk):
            tn = half_n
        elif half_k < tk:
            tk = half_k
        else:
            break
    return tk, tn


def _plan(tile_m: int, k: int, n: int, itemsize: int):
    """(tile_k, tile_n) of the row-times-matrix kernel: the whole matrix of
    a group in VMEM where the blocks fit (it is then read ONCE a run of
    tiles of that group, x and out once). Blocks are double-buffered, the
    float32 accumulator is not."""
    return _fit(lambda tk, tn: (2 * (tile_m * tk + tk * tn + tile_m * tn) * itemsize
                                + 4 * tile_m * tn), k, n)


def _plan_t(tile_m: int, k: int, n: int, itemsize: int):
    """(tile_k, tile_n) of the weights'-gradient kernel: a (tile_k, tile_n)
    float32 accumulator and its double-buffered output block."""
    return _fit(lambda tk, tn: 2 * tile_m * (tk + tn) * itemsize + 3 * 4 * tk * tn,
                k, n)


def _live(i, n_tiles):
    """The tile whose blocks grid step i maps: itself, or the last live one
    (no new DMA) once the live tiles are done."""
    return jnp.minimum(i, n_tiles[0] - 1)


def _params():
    """Every axis in order: a tile's group, and so its blocks, follow from
    the tile before it."""
    return pltpu.CompilerParams(dimension_semantics=("arbitrary",) * 3,
                                vmem_limit_bytes=_VMEM_LIMIT)


# -- rows of a group times its matrix ---------------------------------------------

def _gmm_kernel(tile_group, n_tiles, x_ref, w_ref, o_ref, acc_ref, *,
                transpose_w: bool, tiles_k: int):
    del tile_group
    i, kk = pl.program_id(0), pl.program_id(2)

    @pl.when(kk == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(i < n_tiles[0])
    def _product():
        dims = (((1,), (1,)), ((), ())) if transpose_w else (((1,), (0,)), ((), ()))
        acc_ref[...] += jax.lax.dot_general(
            x_ref[...], w_ref[...], dims, preferred_element_type=jnp.float32)

    @pl.when(kk == tiles_k - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _gmm(x, w, tile_group, n_tiles, *, tile_m: int, transpose_w: bool,
         name: str, interpret: bool):
    """x: (rows, k); w: (groups, k, n), or (groups, n, k) with transpose_w.
    -> (rows, n) in x's type; zeros in the tiles past n_tiles."""
    rows, k = x.shape
    n = w.shape[1] if transpose_w else w.shape[2]
    tk, tn = _plan(tile_m, k, n, x.dtype.itemsize)
    tiles_k = k // tk

    def x_map(i, j, kk, tg, nt):
        return _live(i, nt), kk

    def w_map(i, j, kk, tg, nt):
        g = tg[_live(i, nt)]
        return (g, j, kk) if transpose_w else (g, kk, j)

    w_block = (None, tn, tk) if transpose_w else (None, tk, tn)
    return pl.pallas_call(
        functools.partial(_gmm_kernel, transpose_w=transpose_w, tiles_k=tiles_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(rows // tile_m, n // tn, tiles_k),
            in_specs=[pl.BlockSpec((tile_m, tk), x_map),
                      pl.BlockSpec(w_block, w_map)],
            out_specs=pl.BlockSpec((tile_m, tn), lambda i, j, kk, tg, nt: (i, j)),
            scratch_shapes=[pltpu.VMEM((tile_m, tn), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((rows, n), x.dtype),
        compiler_params=_params(),
        interpret=interpret, name=name,
    )(tile_group, n_tiles, x, w)


# -- the matrices' gradient: a group's rows, transposed, times its rows -------

def _tgmm_kernel(tile_group, n_tiles, x_ref, dy_ref, o_ref, acc_ref):
    i = pl.program_id(2)
    n = n_tiles[0]
    last_tile = tile_group.shape[0] - 1
    g = tile_group[i]
    first = jnp.logical_or(i == 0, tile_group[jnp.maximum(i - 1, 0)] != g)
    last = jnp.logical_or(i == n - 1, tile_group[jnp.minimum(i + 1, last_tile)] != g)
    live = i < n

    @pl.when(jnp.logical_and(live, first))
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _product():
        acc_ref[...] += jax.lax.dot_general(
            x_ref[...], dy_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_and(live, last))
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _tgmm(x, dy, tile_group, n_tiles, groups: int, *, tile_m: int, out_dtype,
          interpret: bool):
    """x: (rows, k); dy: (rows, n) -> (groups, k, n) in out_dtype. Every
    group owns a tile, so every block of the output is written."""
    rows, k = x.shape
    n = dy.shape[1]
    tk, tn = _plan_t(tile_m, k, n, x.dtype.itemsize)
    return pl.pallas_call(
        _tgmm_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(k // tk, n // tn, rows // tile_m),
            in_specs=[pl.BlockSpec((tile_m, tk), lambda a, b, i, tg, nt: (_live(i, nt), a)),
                      pl.BlockSpec((tile_m, tn), lambda a, b, i, tg, nt: (_live(i, nt), b))],
            out_specs=pl.BlockSpec((None, tk, tn),
                                   lambda a, b, i, tg, nt: (tg[_live(i, nt)], a, b)),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((groups, k, n), out_dtype),
        compiler_params=_params(),
        interpret=interpret, name="moe_tgmm_dw",
    )(tile_group, n_tiles, x, dy)


# -- the op -------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _grouped(x, w, tile_group, n_tiles, tile_m, interpret):
    return _gmm(x, w.astype(x.dtype), tile_group, n_tiles, tile_m=tile_m,
                transpose_w=False, name="moe_gmm_fwd", interpret=interpret)


def _grouped_fwd(x, w, tile_group, n_tiles, tile_m, interpret):
    return (_grouped(x, w, tile_group, n_tiles, tile_m, interpret),
            (x, w, tile_group, n_tiles))


def _grouped_bwd(tile_m, interpret, res, dout):
    x, w, tile_group, n_tiles = res
    dout = dout.astype(x.dtype)
    dx = _gmm(dout, w.astype(x.dtype), tile_group, n_tiles, tile_m=tile_m,
              transpose_w=True, name="moe_gmm_dx", interpret=interpret)
    dw = _tgmm(x, dout, tile_group, n_tiles, w.shape[0], tile_m=tile_m,
               out_dtype=w.dtype, interpret=interpret)
    return dx, dw, None, None


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def grouped_matmul(x, w, tile_group, n_tiles, *, tile_m: int,
                   interpret: bool | None = None):
    """out[rows of group g] = x[rows of group g] @ w[g].

    x: (rows, k), tile-aligned as the module's head describes (`group_tiles`
    gives `tile_group` and `n_tiles` for a `tile_m` from `tile_rows`), its
    padding rows zero; w: (groups, k, n). Returns (rows, n) in x's type,
    zeros past the live tiles. Differentiable in x and w."""
    if x.shape[0] % tile_m:
        raise ValueError(f"{x.shape[0]} rows are no multiple of tile_m {tile_m}")
    if interpret is None:
        interpret = _auto_interpret()
    return _grouped(x, w, tile_group, n_tiles, tile_m, interpret)
