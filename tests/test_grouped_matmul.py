"""tpunet.ops.grouped_matmul against a loop of jnp.dot over the groups, in
Pallas' interpreter: the product, the product against the transposed
matrices (the rows' gradient) and the per-group outer product (the
matrices' gradient), with empty groups, groups that end inside a tile, and
a row count that is no multiple of the tile."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpunet.ops import grouped_matmul as gm

CASES = {
    # sizes, k, n, dtype, the most rows the buffer must allow
    "tiny_f32": ([5, 0, 17, 3], 32, 24, jnp.float32, 25),
    "all_in_one_group": ([0, 40, 0], 16, 8, jnp.float32, 40),
    "every_group_empty": ([0, 0, 0], 16, 8, jnp.float32, 12),
    "rows_no_multiple_of_the_tile": ([700, 0, 30, 1000, 0], 256, 128, jnp.bfloat16, 2000),
    "k_and_n_tiled": ([513, 511], 256, 384, jnp.bfloat16, 1024),
}


def _layout(sizes, dtype, max_rows):
    tile_m = gm.tile_rows(max_rows, dtype)
    rows = gm.buffer_rows(max_rows, len(sizes), tile_m)
    starts, tile_group, n_tiles = gm.group_tiles(
        jnp.asarray(sizes, jnp.int32), tile_m, rows)
    return tile_m, rows, np.asarray(starts), tile_group, n_tiles


def _place(rng, sizes, starts, rows, width, dtype):
    """A buffer whose group g holds sizes[g] random rows from starts[g] on,
    zeros elsewhere (the layout's contract)."""
    x = np.zeros((rows, width), np.float32)
    for g, size in enumerate(sizes):
        x[starts[g]:starts[g] + size] = rng.normal(size=(size, width))
    return jnp.asarray(x, dtype)


def _loop(x, w, sizes, starts):
    out = jnp.zeros((x.shape[0], w.shape[2]), jnp.float32)
    for g, size in enumerate(sizes):
        a = int(starts[g])
        out = out.at[a:a + size].set(jnp.dot(
            x[a:a + size].astype(jnp.float32),
            w[g].astype(x.dtype).astype(jnp.float32), precision="highest"))
    return out


@pytest.mark.parametrize("case", CASES)
def test_layout_fits_the_buffer_and_keeps_groups_apart(case):
    sizes, _, _, dtype, max_rows = CASES[case]
    tile_m, rows, starts, tile_group, n_tiles = _layout(sizes, dtype, max_rows)
    assert sum(sizes) <= max_rows and rows % tile_m == 0
    assert int(n_tiles[0]) * tile_m <= rows
    ends = [int(starts[g]) + max(size, 1) for g, size in enumerate(sizes)]
    for g in range(len(sizes)):
        assert starts[g] % tile_m == 0
        assert ends[g] <= (starts[g + 1] if g + 1 < len(sizes)
                           else int(n_tiles[0]) * tile_m)
        for tile in range(int(starts[g]) // tile_m, -(-ends[g] // tile_m)):
            assert int(tile_group[tile]) == g


@pytest.mark.parametrize("case", CASES)
def test_grouped_matmul_and_its_gradients_match_a_loop(case, monkeypatch):
    sizes, k, n, dtype, max_rows = CASES[case]
    if case == "k_and_n_tiled":  # force tiles_k = 2 and tiles_n = 3
        monkeypatch.setattr(gm, "_VMEM_BLOCKS", 1_000_000)
        assert gm._plan(512, k, n, 2) == (128, 128)
        assert gm._plan_t(512, k, n, 2) == (128, 128)
    tile_m, rows, starts, tile_group, n_tiles = _layout(sizes, dtype, max_rows)
    rng = np.random.default_rng(0)
    x = _place(rng, sizes, starts, rows, k, dtype)
    w = jnp.asarray(rng.normal(size=(len(sizes), k, n)), jnp.float32)
    ct = _place(rng, sizes, starts, rows, n, jnp.float32)

    def kernel(x, w):
        return gm.grouped_matmul(x, w, tile_group, n_tiles, tile_m=tile_m)

    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    out, want = kernel(x, w), _loop(x, w, sizes, starts)
    assert out.dtype == x.dtype and out.shape == (rows, n)
    scale = float(jnp.max(jnp.abs(want))) + 1.0
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(want),
                               atol=tol * scale)
    live = int(n_tiles[0]) * tile_m
    assert not np.asarray(out[live:], np.float32).any()  # zeros past the live tiles

    loss = lambda fn: lambda x, w: jnp.sum(fn(x, w).astype(jnp.float32) * ct)  # noqa: E731
    dx, dw = jax.grad(loss(kernel), (0, 1))(x, w)
    rx, rw = jax.grad(loss(lambda x, w: _loop(x, w, sizes, starts)), (0, 1))(
        x.astype(jnp.float32), w)
    assert dx.dtype == x.dtype and dw.dtype == jnp.float32
    for got, ref in ((dx, rx), (dw, rw)):
        scale = float(jnp.max(jnp.abs(ref))) + 1.0
        np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(ref),
                                   atol=tol * scale)
    for g, size in enumerate(sizes):  # an empty group's matrix gets zeros
        if size == 0:
            assert not np.asarray(dw[g]).any()


def test_rows_that_are_no_multiple_of_the_tile_are_refused():
    with pytest.raises(ValueError, match="multiple of tile_m"):
        gm.grouped_matmul(jnp.zeros((20, 8)), jnp.zeros((2, 8, 8)),
                          jnp.zeros((2,), jnp.int32), jnp.ones((1,), jnp.int32),
                          tile_m=16)
