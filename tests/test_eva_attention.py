"""EVA chunk-summary attention (tpunet/ops/eva_attention.py) and what the
model and the trainer gained for it: the kernels against the plain
equations, the model through make_train_step against a dense-mask model
written here, the shifted targets of several prediction heads against a
loop, and the new Transformer options at their defaults against today's
outputs. CPU, tiny widths; the kernels run in Pallas' interpreter."""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tpunet.models import Transformer
from tpunet.ops.eva_attention import (_plan, eva_attention,
                                      eva_attention_reference)
from tpunet.train import TrainState, make_train_step
from tpunet.train.trainer import multi_head_targets

# Float32 operands at `highest` on both sides: what is left is the order of
# the sums (blockwise online softmax against one dense softmax; two merged
# partial softmaxes against one), a few float32 roundings of O(1) values.
RTOL = 1e-5

# (seq, window, chunk, block_q, block_k, heads, head_dim); a sequence of one
# window has no summary to see, one of three sees one and two windows' worth
SHAPES = {
    "w8c2-one-window": (8, 8, 2, 8, 8, 2, 8),
    "w8c2-three-windows": (24, 8, 2, 4, 4, 2, 8),
    "w8c2-uneven-blocks": (24, 8, 2, 8, 2, 2, 8),
    "w256c16-one-window": (256, 256, 16, 128, 128, 2, 64),
    "w256c16-three-windows": (768, 256, 16, 128, 64, 2, 64),
    "ragged-dense-fallback": (20, 8, 2, 4, 4, 2, 8),
}


def _inputs(seq, heads, dim, batch=2, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seq), 6)
    q, k, v = (jax.random.normal(ks[i], (batch, seq, heads, dim), dtype)
               for i in range(3))
    phi, mu = (0.3 * jax.random.normal(ks[i], (heads, dim)) for i in (3, 4))
    weight = jax.random.normal(ks[5], (batch, seq, heads, dim))
    return (q, k, v, phi, mu), weight


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return np.abs(a - b).max() / max(np.abs(b).max(), 1e-30)


@pytest.mark.parametrize("name", SHAPES)
def test_forward_matches_the_equations(name):
    seq, window, chunk, bq, bk, heads, dim = SHAPES[name]
    args, _ = _inputs(seq, heads, dim)
    took_kernels = _plan(seq, window, chunk, bq, bk, True, jnp.float32) is not None
    assert took_kernels == (not name.startswith("ragged"))
    out = eva_attention(*args, window, chunk, bq, bk)
    assert _rel(out, eva_attention_reference(*args, window, chunk)) < RTOL


@functools.lru_cache(maxsize=None)
def _gradients(name):
    """(kernels', equations') gradients of one weighted sum with respect to
    q, k, v, phi, mu; one backward pass a shape serves its five cases."""
    seq, window, chunk, bq, bk, heads, dim = SHAPES[name]
    args, weight = _inputs(seq, heads, dim)

    def grads(fn):
        return jax.grad(lambda *a: jnp.sum(fn(*a) * weight), (0, 1, 2, 3, 4))(*args)

    return (grads(lambda *a: eva_attention(*a, window, chunk, bq, bk)),
            grads(lambda *a: eva_attention_reference(*a, window, chunk)))


@pytest.mark.parametrize("wrt", ["q", "k", "v", "phi", "mu"])
@pytest.mark.parametrize("name", SHAPES)
def test_gradient_matches_the_equations(name, wrt):
    n = "q k v phi mu".split().index(wrt)
    got, want = _gradients(name)
    assert _rel(got[n], want[n]) < RTOL


def test_first_window_ignores_the_summaries():
    """Window 0 sees no summary: its rows equal plain causal attention, and
    phi and mu cannot move them (weight 0, not 0/0)."""
    from tpunet.ops import attention_reference

    (q, k, v, phi, mu), _ = _inputs(24, 2, 8)
    out = eva_attention(q, k, v, phi, mu, 8, 2, 4, 4)
    assert np.isfinite(np.asarray(out)).all()
    plain = attention_reference(q[:, :8], k[:, :8], v[:, :8], causal=True)
    assert _rel(out[:, :8], plain) < RTOL
    moved = eva_attention(q, k, v, 5.0 * phi, mu + 1.0, 8, 2, 4, 4)
    np.testing.assert_array_equal(np.asarray(moved[:, :8]), np.asarray(out[:, :8]))
    assert _rel(moved[:, 8:], out[:, 8:]) > 1e-3


def test_bfloat16_operands_stay_close():
    """bfloat16 q, k, v as the model hands them over: products of bfloat16
    operands summed in float32, so the distance is bfloat16's own (2^-8)."""
    args, _ = _inputs(512, 2, 64, dtype=jnp.bfloat16)
    out = eva_attention(*args, 256, 16, 128, 128)
    assert out.dtype == jnp.bfloat16
    assert _rel(out, eva_attention_reference(*args, 256, 16)) < 2e-2


@pytest.mark.parametrize("bad", ["gqa", "window-not-chunks"])
def test_refused_shapes(bad):
    (q, k, v, phi, mu), _ = _inputs(16, 2, 8)
    with pytest.raises(ValueError):
        if bad == "gqa":
            eva_attention(q, k[:, :, :1], v[:, :, :1], phi, mu, 8, 2)
        else:
            eva_attention(q, k, v, phi, mu, 8, 3)


# -- several prediction heads ----------------------------------------------------

@pytest.mark.parametrize("heads", [1, 4, 8])
def test_multi_head_targets_against_a_loop(heads):
    labels = np.arange(2 * 11).reshape(2, 11) * 3 % 17
    targets, inside = multi_head_targets(jnp.asarray(labels), heads)
    assert targets.shape == (2, 11, heads) and inside.shape == (11, heads)
    for t in range(11):
        for j in range(heads):
            assert bool(inside[t, j]) == (t + j < 11)
            if t + j < 11:
                assert (np.asarray(targets[:, t, j]) == labels[:, t + j]).all()
    assert int(inside.sum()) == sum(11 - j for j in range(heads))


# -- the model, against a dense-mask model written here ---------------------------

TOY = dict(vocab=40, d_model=32, n_layers=2, n_heads=2, d_ff=48, mlp_impl="swiglu",
           attn_impl="eva", eva_window=8, eva_chunk=2, norm_eps=1e-5,
           norm_unit_offset=True, rope_theta=1e5, residual_dtype=jnp.float32,
           n_pred_heads=4, compute_dtype=jnp.float32, remat=True)


def _dense_model_loss(params, tokens, labels, cfg=TOY):
    """EvaByte's forward pass and loss straight from the equations, one row
    at a time, with an (s, s + s/chunk) mask; nothing of tpunet.ops."""
    hi = jax.lax.Precision.HIGHEST
    h, w, c = cfg["n_heads"], cfg["eva_window"], cfg["eva_chunk"]
    dh = cfg["d_model"] // h

    def norm(x, g):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + cfg["norm_eps"]) * (1 + g)

    def rope(x):
        s, half = x.shape[0], dh // 2
        freq = jnp.exp(-math.log(cfg["rope_theta"]) * jnp.arange(half) / half)
        ang = jnp.arange(s)[:, None] * freq
        cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
        a, b = x[..., :half], x[..., half:]
        return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)

    def row(toks, labs):
        s = toks.shape[0]
        x = params["embed"][toks]
        t, m = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
        keep = jnp.concatenate(
            [(m <= t) & (m // w == t // w),
             jnp.arange(s // c)[None, :] < (w // c) * (t // w)], 1)
        for i in range(cfg["n_layers"]):
            p = params[f"block{i}"]
            u = norm(x, p["norm1"]["scale"])
            q, k, v = (jnp.dot(u, p["attn"][n]["kernel"], precision=hi).reshape(s, h, dh)
                       for n in "qkv")
            q, k = rope(q), rope(k)
            kc, vc = k.reshape(s // c, c, h, dh), v.reshape(s // c, c, h, dh)
            pi = jax.nn.softmax(jnp.einsum("nchd,hd->nch", kc, p["attn"]["adaptive_phi"],
                                           precision=hi), axis=1)
            khat = jnp.einsum("nch,nchd->nhd", pi, kc, precision=hi) + p["attn"]["adaptive_mu_k"]
            vhat = jnp.einsum("nch,nchd->nhd", pi, vc, precision=hi)
            scores = jnp.einsum("qhd,khd->hqk", q, jnp.concatenate([k, khat]),
                                precision=hi) / math.sqrt(dh)
            prob = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), -1)
            o = jnp.einsum("hqk,khd->qhd", prob, jnp.concatenate([v, vhat]), precision=hi)
            x = x + jnp.dot(o.reshape(s, h * dh), p["attn"]["out"]["kernel"], precision=hi)
            u = norm(x, p["norm2"]["scale"])
            gate = jnp.dot(u, p["mlp"]["gate"]["kernel"], precision=hi)
            up = jnp.dot(u, p["mlp"]["up"]["kernel"], precision=hi)
            x = x + jnp.dot(jax.nn.silu(gate) * up, p["mlp"]["down"]["kernel"], precision=hi)
        logits = jnp.dot(norm(x, params["norm_f"]["scale"]), params["lm_head"]["kernel"],
                         precision=hi).reshape(s, cfg["n_pred_heads"], cfg["vocab"])
        total, count = 0.0, 0
        for j in range(cfg["n_pred_heads"]):
            lg = jax.nn.log_softmax(logits[: s - j, j])
            total = total - jnp.sum(jnp.take_along_axis(lg, labs[j:, None], 1))
            count += s - j
        return total / count

    return jnp.mean(jax.vmap(row)(tokens, labels))


def test_eva_model_trains_like_the_dense_mask_model():
    model = Transformer(**TOY)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0, TOY["vocab"])
    labels = jnp.roll(tokens, -1, axis=1)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    assert params["block0"]["attn"]["adaptive_phi"].shape == (2, 16)
    assert float(jnp.abs(params["norm_f"]["scale"]).max()) == 0.0  # offsets from 1
    assert model.apply({"params": params}, tokens).shape == (2, 24, 4, TOY["vocab"])
    tx = optax.sgd(0.1)
    state = TrainState(params, tx.init(params), jnp.zeros((), jnp.int32))
    step = make_train_step(model, tx, donate=False)
    ref_params = params
    for _ in range(2):
        state, loss = step(state, tokens, labels, jax.random.PRNGKey(2))
        ref_loss, grads = jax.value_and_grad(_dense_model_loss)(ref_params, tokens, labels)
        ref_params = jax.tree.map(lambda p, g: p - 0.1 * g, ref_params, grads)
        assert abs(float(loss) - float(ref_loss)) < RTOL * abs(float(ref_loss))
    for got, want in zip(jax.tree.leaves(state.params), jax.tree.leaves(ref_params)):
        assert _rel(got, want) < 1e-4  # two steps of float32 sums in another order


@pytest.mark.parametrize("what", ["decode", "fused_xent", "features_only"])
def test_eva_and_several_heads_refuse_what_they_cannot_do(what):
    tokens = jnp.zeros((1, 16), jnp.int32)
    if what == "decode":
        model = Transformer(**{**TOY, "n_pred_heads": 1, "decode": True, "remat": False})
        with pytest.raises(ValueError, match="decode=True does not support"):
            model.init(jax.random.PRNGKey(0), tokens)
    elif what == "features_only":
        with pytest.raises(ValueError, match="n_pred_heads"):
            Transformer(**TOY).init(jax.random.PRNGKey(0), tokens, features_only=True)
    else:
        model = Transformer(**TOY)
        params = jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)["params"]
        tx = optax.sgd(0.1)
        state = TrainState(params, jax.eval_shape(tx.init, params), jnp.zeros((), jnp.int32))
        step = make_train_step(model, tx, fused_xent_block=8)
        with pytest.raises(ValueError, match="fused_xent_block"):
            jax.eval_shape(step, state, tokens, tokens, jax.random.PRNGKey(0))


# -- every new option at its default is today's model ------------------------------

MISTRAL_TOY = dict(vocab=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2, d_ff=48,
                   mlp_impl="swiglu", attn_window=8, attn_impl="reference")
# logits[1, -1, :6] and sum(|logits|) of that model with compute_dtype float32 on
# tokens (arange(32).reshape(2, 16) * 7) % 64 and PRNGKey(0), computed at the
# commit before these options existed (c3341d7)
BEFORE = ([-0.40342384576797485, 0.41820287704467773, -0.07493765652179718,
           0.7356053590774536, 0.8778401017189026, -0.0449262298643589],
          1601.0888671875)


def _toy_logits(dtype, **options):
    model = Transformer(**MISTRAL_TOY, compute_dtype=dtype, **options)
    tokens = (jnp.arange(2 * 16).reshape(2, 16) * 7) % 64
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    return params, np.asarray(model.apply({"params": params}, tokens))


def test_defaults_give_the_outputs_of_the_commit_before():
    """To 1e-6 and not to the bit: another CPU may sum a matmul in another
    order. The bit-for-bit statement is the next test's."""
    _, out = _toy_logits(jnp.float32)
    np.testing.assert_allclose(out[1, -1, :6], BEFORE[0], rtol=1e-6)
    np.testing.assert_allclose(float(jnp.sum(jnp.abs(out))), BEFORE[1], rtol=1e-6)


@pytest.mark.parametrize("option", [
    {"norm_eps": 1e-6}, {"norm_unit_offset": False}, {"rope_theta": 10000.0},
    {"residual_dtype": None}, {"residual_dtype": jnp.bfloat16}, {"n_pred_heads": 1},
    {"eva_window": 2048, "eva_chunk": 16},
])
def test_each_default_is_bit_for_bit_the_model_without_the_option(option):
    params, plain = _toy_logits(jnp.bfloat16)
    same_params, out = _toy_logits(jnp.bfloat16, **option)
    assert jax.tree.structure(params) == jax.tree.structure(same_params)
    np.testing.assert_array_equal(out, plain)


@pytest.mark.parametrize("option,moves", [
    ({"norm_eps": 1e-2}, True), ({"rope_theta": 1e5}, True),
    ({"residual_dtype": jnp.float32}, True), ({"norm_unit_offset": True}, False),
])
def test_each_option_reaches_the_model(option, moves):
    """A value other than the default changes the logits; the unit offset
    changes the stored scales (zeros for ones) and not the function."""
    params, plain = _toy_logits(jnp.bfloat16)
    other, out = _toy_logits(jnp.bfloat16, **option)
    assert (np.abs(out - plain).max() > 0) == moves
    if not moves:
        assert float(other["norm_f"]["scale"].max()) == 0.0
        assert float(params["norm_f"]["scale"].min()) == 1.0
