"""Numerics tests for tpunet.ops (Pallas kernels, interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpunet.ops import attention_reference, flash_attention


def _qkv(rng, b, s, h, d, dtype=jnp.float32):
    ks = jax.random.split(rng, 3)
    shape = (b, s, h, d)
    return tuple(jax.random.normal(k, shape, dtype) for k in ks)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_reference(causal):
    q, k, v = _qkv(jax.random.PRNGKey(0), 2, 128, 2, 16)
    out = flash_attention(q, k, v, causal, block_q=32, block_k=32)
    ref = attention_reference(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_flash_bf16():
    q, k, v = _qkv(jax.random.PRNGKey(1), 1, 64, 4, 32, jnp.bfloat16)
    out = flash_attention(q, k, v, True, block_q=16, block_k=16)
    ref = attention_reference(q, k, v, True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=3e-2, rtol=3e-2
    )


def test_flash_uneven_falls_back():
    # 100 doesn't tile by 32 — must silently take the reference path.
    q, k, v = _qkv(jax.random.PRNGKey(2), 1, 100, 1, 8)
    out = flash_attention(q, k, v, False, block_q=32, block_k=32)
    ref = attention_reference(q, k, v, False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_grad_matches_reference(causal):
    q, k, v = _qkv(jax.random.PRNGKey(3), 1, 64, 2, 8)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal, 32, 32) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("bq,bk", [(32, 16), (16, 16), (64, 32)])
def test_flash_grad_unequal_blocks(bq, bk):
    # The dkv kernel's causal q-block lower bound must be right for every
    # block_q/block_k ratio the fwd accepts (block_q % block_k == 0).
    q, k, v = _qkv(jax.random.PRNGKey(5), 1, 64, 2, 8)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, bq, bk) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, True) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5, rtol=5e-5)


def test_flash_causal_cross_attention_falls_back():
    # sq != sk under causal would run the kernel's k-loop out of bounds;
    # must take the reference path and stay correct.
    ks = jax.random.split(jax.random.PRNGKey(8), 3)
    q = jax.random.normal(ks[0], (1, 128, 2, 8))
    k = jax.random.normal(ks[1], (1, 64, 2, 8))
    v = jax.random.normal(ks[2], (1, 64, 2, 8))
    out = flash_attention(q, k, v, True, block_q=32, block_k=32)
    ref = attention_reference(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_flash_grad_ragged_fallback():
    # 100 doesn't tile: the VJP must take the einsum fallback and still match.
    q, k, v = _qkv(jax.random.PRNGKey(6), 1, 100, 1, 8)
    gf = jax.grad(lambda q: jnp.sum(flash_attention(q, k, v, True, 32, 32) ** 2))(q)
    gr = jax.grad(lambda q: jnp.sum(attention_reference(q, k, v, True) ** 2))(q)
    np.testing.assert_allclose(np.asarray(gf), np.asarray(gr), atol=5e-5, rtol=5e-5)


def test_flash_grad_bf16_under_jit():
    q, k, v = _qkv(jax.random.PRNGKey(7), 1, 64, 2, 16, jnp.bfloat16)

    @jax.jit
    def g(q, k, v):
        return jax.grad(lambda q, k, v: jnp.sum(
            flash_attention(q, k, v, True, 32, 32).astype(jnp.float32) ** 2
        ), argnums=(0, 1, 2))(q, k, v)

    gf = g(q, k, v)
    gr = jax.grad(lambda q, k, v: jnp.sum(
        attention_reference(q, k, v, True).astype(jnp.float32) ** 2
    ), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        assert a.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), atol=1e-1, rtol=1e-1
        )


def test_flash_under_jit():
    q, k, v = _qkv(jax.random.PRNGKey(4), 1, 64, 1, 16)
    f = jax.jit(lambda q, k, v: flash_attention(q, k, v, True, 32, 32))
    np.testing.assert_allclose(
        np.asarray(f(q, k, v)),
        np.asarray(attention_reference(q, k, v, True)),
        atol=2e-5, rtol=2e-5,
    )


def _gqa_ref(q, k, v, causal):
    group = q.shape[2] // k.shape[2]
    return attention_reference(
        q, jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2), causal
    )


@pytest.mark.parametrize("causal", [False, True])
def test_flash_gqa_matches_repeated_reference(causal):
    rng = jax.random.split(jax.random.PRNGKey(10), 3)
    q = jax.random.normal(rng[0], (2, 128, 8, 16))
    k = jax.random.normal(rng[1], (2, 128, 2, 16))
    v = jax.random.normal(rng[2], (2, 128, 2, 16))
    out = flash_attention(q, k, v, causal, block_q=32, block_k=32)
    ref = _gqa_ref(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_gqa_grads_match_repeated_reference(causal):
    rng = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(rng[0], (1, 64, 4, 8))
    k = jax.random.normal(rng[1], (1, 64, 2, 8))
    v = jax.random.normal(rng[2], (1, 64, 2, 8))

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal, 32, 32) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_gqa_ref(q, k, v, causal) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    assert gf[1].shape == k.shape and gf[2].shape == v.shape
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-5)


def test_flash_gqa_ragged_falls_back():
    rng = jax.random.split(jax.random.PRNGKey(12), 3)
    q = jax.random.normal(rng[0], (1, 100, 4, 8))  # 100: no tiling
    k = jax.random.normal(rng[1], (1, 100, 2, 8))
    v = jax.random.normal(rng[2], (1, 100, 2, 8))

    out = flash_attention(q, k, v, True, block_q=32, block_k=32)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_gqa_ref(q, k, v, True)), atol=2e-5, rtol=2e-5
    )
    g = jax.grad(lambda k: jnp.sum(flash_attention(q, k, v, True, 32, 32)))(k)
    gr = jax.grad(lambda k: jnp.sum(_gqa_ref(q, k, v, True)))(k)
    assert g.shape == k.shape
    np.testing.assert_allclose(np.asarray(g), np.asarray(gr), atol=5e-5, rtol=5e-5)


def test_flash_rejects_indivisible_heads():
    rng = jax.random.split(jax.random.PRNGKey(13), 3)
    q = jax.random.normal(rng[0], (1, 64, 4, 8))
    k = jax.random.normal(rng[1], (1, 64, 3, 8))
    v = jax.random.normal(rng[2], (1, 64, 3, 8))
    with pytest.raises(ValueError, match="divisible"):
        flash_attention(q, k, v, True)


@pytest.mark.parametrize("window", [1, 7, 32, 64, 200])
def test_flash_window_matches_reference(window):
    q, k, v = _qkv(jax.random.PRNGKey(20), 1, 128, 2, 16)
    out = flash_attention(q, k, v, True, 32, 32, window=window)
    ref = attention_reference(q, k, v, True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("window", [16, 48])
def test_flash_window_grads_match_reference(window):
    q, k, v = _qkv(jax.random.PRNGKey(21), 1, 128, 2, 8)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, 32, 32,
                                       window=window) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, True, window=window) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-5)


def test_flash_window_with_gqa():
    rng = jax.random.split(jax.random.PRNGKey(22), 3)
    q = jax.random.normal(rng[0], (1, 128, 4, 8))
    k = jax.random.normal(rng[1], (1, 128, 2, 8))
    v = jax.random.normal(rng[2], (1, 128, 2, 8))
    out = flash_attention(q, k, v, True, 32, 32, window=40)
    ref = attention_reference(q, jnp.repeat(k, 2, axis=2),
                              jnp.repeat(v, 2, axis=2), True, window=40)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    gk = jax.grad(lambda k: jnp.sum(
        flash_attention(q, k, v, True, 32, 32, window=40)))(k)
    gkr = jax.grad(lambda k: jnp.sum(attention_reference(
        q, jnp.repeat(k, 2, axis=2), jnp.repeat(v, 2, axis=2), True,
        window=40)))(k)
    np.testing.assert_allclose(np.asarray(gk), np.asarray(gkr),
                               atol=5e-5, rtol=5e-5)


def test_flash_window_requires_causal():
    q, k, v = _qkv(jax.random.PRNGKey(23), 1, 64, 1, 8)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, False, window=8)


# -- the block step over the shapes of tile, window and group the plan meets ---

def _out_and_grads(attn, q, k, v):
    def loss(q, k, v):
        o = attn(q, k, v)
        return jnp.sum(o.astype(jnp.float32) ** 2), o

    (_, o), grads = jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(
        q, k, v)
    return (o, *grads)


# (seq, heads, kv heads, block_q, block_k, window)
_BLOCK_STEP_CASES = {
    "window_no_multiple_of_tile": (128, 2, 2, 32, 32, 40),
    "window_smaller_than_tile": (128, 2, 2, 32, 32, 7),
    "window_larger_than_seq": (128, 2, 2, 32, 32, 200),
    "block_q_over_block_k": (128, 2, 2, 64, 32, 48),
    "block_q_over_block_k_no_window": (128, 2, 2, 64, 16, None),
    "gqa4_window": (128, 4, 1, 32, 32, 40),
    # window 16 under 32 x 32 tiles: a q-block sees the tile before it (the
    # window's edge) and its own (the diagonal), a boundary in both
    "every_tile_a_boundary": (128, 2, 2, 32, 32, 16),
    # window 128: edge tile, three tiles wholly inside the band, the diagonal
    "whole_tiles_inside_the_band": (256, 2, 2, 32, 32, 128),
    "whole_tiles_no_window": (256, 2, 2, 64, 32, None),
    "tiles_from_the_plan": (256, 4, 2, None, None, 96),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", list(_BLOCK_STEP_CASES))
def test_flash_block_step_matches_reference(case, dtype):
    """Forward and all three gradients against the plain reference."""
    s, h, kv, bq, bk, window = _BLOCK_STEP_CASES[case]
    rng = jax.random.split(jax.random.PRNGKey(30), 3)
    q = jax.random.normal(rng[0], (1, s, h, 16), dtype)
    k = jax.random.normal(rng[1], (1, s, kv, 16), dtype)
    v = jax.random.normal(rng[2], (1, s, kv, 16), dtype)
    got = _out_and_grads(
        lambda q, k, v: flash_attention(q, k, v, True, bq, bk, window=window),
        q, k, v)
    want = _out_and_grads(
        lambda q, k, v: attention_reference(
            q, jnp.repeat(k, h // kv, axis=2), jnp.repeat(v, h // kv, axis=2),
            True, window=window),
        q, k, v)
    tol = 5e-5 if dtype == jnp.float32 else 6e-2
    for name, a, b in zip(("o", "dq", "dk", "dv"), got, want):
        assert a.dtype == dtype and a.shape == b.shape, name
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=tol, rtol=tol, err_msg=name)


def _visible(qpos, kpos, causal, window):
    return (not causal) or (kpos <= qpos and
                            (window is None or qpos - kpos < window))


@pytest.mark.parametrize("s,bq,bk,causal,window", [
    (256, 32, 32, True, None), (256, 64, 16, True, None),
    (256, 32, 32, True, 1), (256, 32, 32, True, 16), (256, 32, 32, True, 33),
    (256, 64, 32, True, 100), (256, 128, 32, True, 128), (256, 32, 32, True, 999),
    (256, 256, 256, True, 96), (256, 64, 32, False, None),
])
def test_flash_loop_bounds_cover_the_band(s, bq, bk, causal, window):
    """The tiles the kernels loop over, against the band itself, pair by
    pair: exactly the tiles that hold a visible (query, key) pair are
    visited, and the q-block view (forward, dQ) and the k-block view (dK/dV)
    visit the same tiles, so no gradient is dropped that the forward used."""
    from tpunet.ops.flash_attention import _k_tiles, _q_tiles

    by_q = {(i, j) for i in range(s // bq)
            for j in range(*_k_tiles(i, bq, bk, s, causal, window, max))}
    by_k = {(i, j) for j in range(s // bk)
            for i in range(*_q_tiles(j, bq, bk, s, causal, window, min))}
    assert by_q == by_k
    for i in range(s // bq):
        for j in range(s // bk):
            holds_a_pair = any(_visible(qp, kp, causal, window)
                               for qp in range(i * bq, (i + 1) * bq)
                               for kp in range(j * bk, (j + 1) * bk))
            assert ((i, j) in by_q) == holds_a_pair, (i, j)


def test_flash_plan():
    """The one place tiles are chosen: documented tiles and count at the
    Mistral cell's shape, one tile for short sequences, the einsum path
    exactly where the fixed 128 x 128 rules took it, explicit blocks win."""
    from tpunet.ops.flash_attention import FlashPlan, _plan

    bf16, f32 = jnp.bfloat16, jnp.float32
    # b2 s8192 h32 kv8 d128 window 4096: 108 tiles a head for 96 tiles' worth
    # of visible pairs (1,584 of 128 x 128 for 1,536 before)
    assert _plan(8192, 8192, bf16, True, 4096) == FlashPlan(512, 512, 108)
    assert _plan(8192, 8192, bf16, True, 4096, 128, 128).tiles == 1584
    assert _plan(8192, 8192, bf16, True, None) == FlashPlan(512, 512, 136)
    assert _plan(2048, 2048, bf16, True, 256) == FlashPlan(512, 512, 7)
    # short sequences are one tile; 640 = 5 x 128 keeps 128
    assert _plan(64, 64, f32, True, None) == FlashPlan(64, 64, 1)
    assert _plan(384, 384, bf16, True, None) == FlashPlan(384, 384, 1)
    assert _plan(640, 640, bf16, True, None)[:2] == (128, 128)
    assert _plan(1024, 4096, bf16, False, None) == FlashPlan(512, 512, 16)
    # explicit blocks win, on the chip too; one alone is also the other's
    assert _plan(8192, 8192, bf16, True, 4096, 1024, 512)[:2] == (1024, 512)
    assert _plan(64, 64, f32, True, None, 16, 16, interpret=True)[:2] == (16, 16)
    assert _plan(2048, 2048, bf16, True, None, 256)[:2] == (256, 256)
    # compiled mode repairs an illegal explicit block as before (block_q
    # lies on lse's lane dim; 16 rows of bf16 keys are a legal sublane tile)
    assert _plan(2048, 2048, bf16, True, None, 16, 16)[:2] == (128, 16)
    # the einsum path, where the old rules took it: ragged, 200 = no
    # multiple of 128, causal cross-attention, mixed ratio under causal
    for args in [(100, 100, f32, False, None, 32, 32, True),
                 (200, 200, bf16, True, None),
                 (200, 200, bf16, True, None, 128, 128),
                 (128, 64, f32, True, None, 32, 32, True),
                 (64, 64, f32, True, None, 16, 32, True),
                 (8192, 8192, bf16, True, 4096, 256, 512)]:
        assert _plan(*args) is None, args
