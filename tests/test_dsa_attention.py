"""tpunet.ops.dsa_attention on the CPU (kernels through Pallas' interpreter,
small shapes): the indexer's scores, the selection against `jax.lax.top_k`
and a stable sort, the attention over the selection against a masked einsum,
forward and backward, and the indexer's loss against plain autodiff."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpunet.ops import dsa_attention as dsa

B, S, H, KV, D, HI, DI, K = 2, 64, 4, 2, 16, 3, 8, 20


def _inputs(seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    shapes = [(B, S, H, D), (B, S, KV, D), (B, S, KV, D), (B, S, HI, DI), (B, S, DI),
              (B, S, HI)]
    return [jax.random.normal(k, s).astype(dtype) for k, s in zip(ks, shapes)]


def _sorted_selection(scores, top_k):
    """The oracle in numpy: a stable sort of each query's causal scores,
    largest first, ties to the lower index; the first min(t + 1, top_k)."""
    scores = np.asarray(scores)
    out = np.zeros(scores.shape, np.int8)
    for b in range(scores.shape[0]):
        for t in range(scores.shape[1]):
            order = np.argsort(-scores[b, t, :t + 1], kind="stable")[:min(t + 1, top_k)]
            out[b, t, order] = 1
    return out


def _top_k_selection(scores, top_k):
    """The same set through `jax.lax.top_k` and a scatter (what the program
    would run if that were the faster way: on the chip it is not)."""
    b, s, _ = scores.shape
    k = min(top_k, s)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    idx = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), k)[1]  # best first
    live = jnp.arange(k)[None, None, :] < jnp.minimum(jnp.arange(s) + 1, k)[None, :, None]
    return jnp.zeros((b, s, s + 1), jnp.int8).at[
        jnp.arange(b)[:, None, None], jnp.arange(s)[None, :, None],
        jnp.where(live, idx, s)].set(1)[..., :s]


@pytest.mark.parametrize("block", [16, 64])
def test_index_scores_kernel_matches_the_einsum_on_the_causal_pairs(block):
    _, _, _, qi, ki, w = _inputs()
    got = dsa.index_scores(qi, ki, w, block=block)
    want = dsa.index_scores_reference(qi, ki, w)
    causal = np.tril(np.ones((S, S), bool))
    np.testing.assert_allclose(np.where(causal, got, 0), np.where(causal, want, 0),
                               atol=1e-5 * float(jnp.max(jnp.abs(want))))


def test_index_scores_pass_no_gradient():
    """The scores are ranked and enter the loss detached: what the indexer
    learns from comes back through `index_loss` alone."""
    _, _, _, qi, ki, w = _inputs()
    grads = jax.grad(lambda a, b, c: jnp.sum(jnp.tril(dsa.index_scores(a, b, c, block=16))),
                     (0, 1, 2))(qi, ki, w)
    assert all(float(jnp.max(jnp.abs(g))) == 0.0 for g in grads)


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "kernel"])
@pytest.mark.parametrize("top_k", [1, 20, 64, 100])
def test_selection_is_top_ks_set(top_k, kernel, monkeypatch):
    """Queries with fewer than top_k earlier keys take them all; no key
    after the query; as many as min(t + 1, top_k) a query."""
    monkeypatch.setattr(dsa, "_SELECT_ROWS", 16)
    scores = jax.random.normal(jax.random.PRNGKey(3), (B, S, S))
    mask, pairs = dsa.select(scores, top_k, kernel=kernel)
    np.testing.assert_array_equal(mask, _sorted_selection(scores, top_k))
    np.testing.assert_array_equal(mask, _top_k_selection(scores, top_k))
    assert not np.triu(np.asarray(mask), 1).any()
    per_query = np.asarray(mask).sum(-1)
    np.testing.assert_array_equal(
        per_query, np.broadcast_to(np.minimum(np.arange(S) + 1, top_k), (B, S)))
    assert int(pairs) == per_query.sum()


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "kernel"])
def test_planted_ties_go_to_the_lower_index(kernel, monkeypatch):
    """Scores on a grid of halves tie at nearly every threshold, at zero
    most of all (as relu-made scores do); whole rows of one value too. What
    lies above the diagonal is garbage the selection must not read."""
    monkeypatch.setattr(dsa, "_SELECT_ROWS", 32)
    scores = jnp.round(jax.random.normal(jax.random.PRNGKey(4), (B, S, S)) * 2) / 2
    scores = jnp.where(scores == 0, 0.0, scores)  # no -0.0: see dsa._sortable
    scores = scores.at[0, 40].set(1.5).at[1, 50].set(0.0).at[1, 51].set(-2.0)
    want = _sorted_selection(scores, K)
    dirty = jnp.where(jnp.tril(jnp.ones((S, S), bool)), scores, jnp.nan)
    for given in (scores, dirty):
        mask, _ = dsa.select(given, K, kernel=kernel)
        np.testing.assert_array_equal(mask, want)
    np.testing.assert_array_equal(_top_k_selection(scores, K), want)
    assert list(np.nonzero(want[0, 40])[0]) == list(range(K))  # all tied: the first K


def test_selection_orders_negative_scores_and_tiny_differences():
    base = jnp.linspace(-3.0, 3.0, S)[None, None, :] * jnp.ones((B, S, 1))
    nudged = jnp.nextafter(base, jnp.inf * jnp.ones_like(base))
    scores = jnp.where(jnp.arange(S)[None, None, :] % 2 == 0, base, nudged) - 1.0
    np.testing.assert_array_equal(dsa.select(scores, 7)[0], _sorted_selection(scores, 7))


def _loss(fn, q, k, v, mask):
    return jnp.sum(fn(q, k, v, mask) * jnp.cos(jnp.arange(D, dtype=jnp.float32)))


@pytest.mark.parametrize("block", [16, 32])
def test_selected_attention_kernels_match_the_masked_einsum(block):
    """Forward, dq, dk and dv; grouped-query (2 query heads a key head). The
    first tiles of a late query hold none of its keys, which is the case the
    running maximum must survive."""
    q, k, v, qi, ki, w = _inputs(1)
    mask, _ = dsa.select(dsa.index_scores_reference(qi, ki, w), 6)
    kernel = lambda q, k, v, m: dsa.selected_attention(q, k, v, m, block=block)  # noqa: E731
    got = kernel(q, k, v, mask)
    want = dsa.selected_attention_reference(q, k, v, mask)
    np.testing.assert_allclose(got, want, atol=2e-6 * float(jnp.max(jnp.abs(want))))
    # by hand for one query: the softmax runs over its kept keys ALONE
    t = S - 1
    kept = np.nonzero(np.asarray(mask[0, t]))[0]
    logits = np.asarray(k[0, kept, 0] @ q[0, t, 0]) / math.sqrt(D)
    p = np.exp(logits - logits.max())
    np.testing.assert_allclose(got[0, t, 0], (p / p.sum()) @ np.asarray(v[0, kept, 0]),
                               atol=1e-5)
    grads = jax.grad(lambda *a: _loss(kernel, *a, mask), (0, 1, 2))(q, k, v)
    wants = jax.grad(lambda *a: _loss(dsa.selected_attention_reference, *a, mask),
                     (0, 1, 2))(q, k, v)
    for g, wnt in zip(grads, wants):
        np.testing.assert_allclose(g, wnt, atol=1e-5 * float(jnp.max(jnp.abs(wnt))))


def test_selected_attention_refuses_what_it_cannot_tile():
    q, k, v, *_ = _inputs()
    mask = jnp.tril(jnp.ones((B, S, S), jnp.int8))
    with pytest.raises(ValueError, match="not tiled"):
        dsa.selected_attention(q, k, v, mask, block=48)
    with pytest.raises(ValueError, match="not divisible"):
        dsa.selected_attention(q[:, :, :3], k, v, mask)


def _plain_index_loss(q, k, mask, qi, ki, w):
    keep = mask != 0
    scores = dsa.index_scores_reference(qi, ki, w)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", q.reshape(B, S, KV, H // KV, D), k) / math.sqrt(D)
    probs = jax.nn.softmax(jnp.where(keep[:, None, None], logits, -1e30), -1)
    p = jnp.where(keep, probs.mean((1, 2)), 0.0)
    log_i = jax.nn.log_softmax(jnp.where(keep, scores, -1e30), -1)
    return jnp.sum(jnp.where(p > 0, p * (jnp.log(jnp.where(p > 0, p, 1.0)) - log_i), 0.0)) / (B * S)


@pytest.mark.parametrize("kernels", [False, True], ids=["xla", "kernels"])
def test_index_loss_and_its_hand_written_backward_match_autodiff(kernels, monkeypatch):
    """The loss a block of 16 queries at a time, its gradient made with it
    and scaled by the cotangent, against the same formula under autodiff;
    as plain XLA, and as the three kernels (which take the attention's
    log-sum-exp and sum p a head at a time)."""
    monkeypatch.setattr(dsa, "_KL_BLOCK", 16)
    monkeypatch.setattr(dsa, "_KL_ROWS", 16)
    monkeypatch.setattr(dsa, "_auto_block", lambda seq: 32)
    q, k, v, qi, ki, w = _inputs(2)
    scores = dsa.index_scores_reference(qi, ki, w)
    mask, _ = dsa.select(scores, K)
    lse = dsa.selected_attention(q, k, v, mask, with_lse=True)[1] if kernels else None
    ours = lambda a, b, c: 3.0 * dsa.index_loss(  # noqa: E731
        q, k, mask, scores, a, b, c, lse=lse)
    plain = lambda a, b, c: 3.0 * _plain_index_loss(q, k, mask, a, b, c)  # noqa: E731
    got, grads = jax.value_and_grad(ours, (0, 1, 2))(qi, ki, w)
    want, wants = jax.value_and_grad(plain, (0, 1, 2))(qi, ki, w)
    assert abs(float(got) - float(want)) < 1e-6 * float(want)
    assert float(ours(qi, ki, w)) == pytest.approx(float(want), rel=1e-6)  # not differentiated
    for g, wnt in zip(grads, wants):
        np.testing.assert_allclose(g, wnt, atol=1e-5 * float(jnp.max(jnp.abs(wnt))))
    # the target and the scores are constants of it
    dq, dk, ds = jax.grad(lambda q, k, s: dsa.index_loss(q, k, mask, s, qi, ki, w, lse=lse),
                          (0, 1, 2))(q, k, scores)
    assert float(jnp.max(jnp.abs(dq))) == float(jnp.max(jnp.abs(dk))) == 0.0
    assert float(jnp.max(jnp.abs(ds))) == 0.0


def test_index_loss_is_zero_when_the_indexer_ranks_as_the_attention_does():
    """KL(p || softmax(I)) = 0 where the indexer's scores are log p: one
    head, so p is that head's softmax, and scores planted as its logits."""
    q, k, *_ = _inputs(5)
    q, k = q[:, :, :1], k[:, :, :1]
    logits = jnp.einsum("bqd,bsd->bqs", q[:, :, 0], k[:, :, 0]) / math.sqrt(D)
    mask, _ = dsa.select(logits, K)
    qi = jnp.zeros((B, S, 1, DI)); ki = jnp.zeros((B, S, DI)); w = jnp.zeros((B, S, 1))
    assert abs(float(dsa.index_loss(q, k, mask, logits, qi, ki, w))) < 1e-6
    flat = dsa.index_loss(q, k, mask, jnp.zeros_like(logits), qi, ki, w)
    assert float(flat) > 1e-2  # a flat indexer is far from a peaked attention
