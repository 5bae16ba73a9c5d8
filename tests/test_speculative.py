"""Speculative decoding — exactness and mechanics.

Three layers of evidence that `speculative_generate` preserves the target
model's distribution:
1. the core accept/residual rule is Monte-Carlo-verified to reproduce the
   target distribution exactly (the Leviathan identity), independent of
   any model;
2. greedy end-to-end output is bitwise `generate`'s, for arbitrary-quality
   drafts (draft quality must affect only throughput);
3. a draft identical to the target accepts every proposal (accept rate 1),
   pinning the acceptance plumbing.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpunet.models import Transformer, generate, speculative_generate
from tpunet.models.generate import (_leading_accepts, _residual_probs,
                                    filtered_logits)


def _tiny(**kw):
    kw.setdefault("vocab", 64)
    kw.setdefault("d_model", 32)
    kw.setdefault("n_layers", 2)
    kw.setdefault("n_heads", 4)
    kw.setdefault("d_ff", 64)
    kw.setdefault("compute_dtype", jnp.float32)
    return Transformer(**kw)


def _params(model, b=2, s=24, seed=1):
    toks = jax.random.randint(jax.random.PRNGKey(0), (b, s), 0, model.vocab)
    return model.init(jax.random.PRNGKey(seed), toks)["params"], toks


def test_accept_residual_rule_reproduces_target_exactly():
    """The identity min(q, p) + (1 - sum min(p, q)) * residual = p, run as
    the actual sampled process: draft from q, accept with prob min(1,
    p/q), else sample the residual. Empirical marginal must match p to
    Monte-Carlo accuracy — this is the theorem the whole scheme rests on,
    tested with no model in the loop."""
    v = 5
    rng = np.random.default_rng(0)
    p = rng.dirichlet(np.ones(v))
    q = rng.dirichlet(np.ones(v))
    n = 200_000
    key = jax.random.PRNGKey(1)
    kd, ka, kr = jax.random.split(key, 3)
    draft = jax.random.categorical(kd, jnp.log(jnp.asarray(q))[None, :],
                                   shape=(n,))
    u = jax.random.uniform(ka, (n,))
    accept = u * jnp.asarray(q)[draft] < jnp.asarray(p)[draft]
    res = _residual_probs(jnp.asarray(p)[None, :], jnp.asarray(q)[None, :])
    resample = jax.random.categorical(kr, jnp.log(res), shape=(n,))
    tok = jnp.where(accept, draft, resample)
    emp = np.bincount(np.asarray(tok), minlength=v) / n
    np.testing.assert_allclose(emp, p, atol=5e-3)
    # Acceptance rate matches its closed form sum min(p, q).
    assert np.asarray(accept).mean() == pytest.approx(
        np.minimum(p, q).sum(), abs=5e-3)


def test_residual_probs_identical_dists_falls_back_to_p():
    p = jnp.asarray([[0.5, 0.25, 0.25]])
    np.testing.assert_allclose(np.asarray(_residual_probs(p, p)), p)


def test_leading_accepts():
    acc = jnp.asarray([[True, True, False, True],
                       [False, True, True, True],
                       [True, True, True, True]])
    assert _leading_accepts(acc).tolist() == [2, 0, 4]


@pytest.mark.parametrize("gamma", [1, 2, 4])
@pytest.mark.parametrize("draft_kind", ["smaller", "different"])
def test_greedy_bitwise_matches_generate(gamma, draft_kind):
    """Greedy speculative output == ancestral greedy, token for token, for
    drafts of arbitrary quality — a bad draft may only slow things down."""
    model = _tiny()
    params, prompt = _params(model)
    if draft_kind == "smaller":
        draft = _tiny(n_layers=1)
        draft_params, _ = _params(draft, seed=7)
    else:  # same shape, unrelated weights: a pathologically bad draft
        draft = _tiny()
        draft_params, _ = _params(draft, seed=99)
    want = generate(model, params, prompt, 12)
    got = speculative_generate(model, params, draft, draft_params, prompt,
                               12, gamma=gamma)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_self_draft_accepts_everything():
    """draft == target => p == q at every position => accept prob 1: every
    round commits gamma+1 tokens and the accept rate reads 1.0."""
    model = _tiny()
    params, prompt = _params(model)
    gamma, new = 3, 13
    out, stats = speculative_generate(
        model, params, model, params, prompt, new, gamma=gamma,
        temperature=0.8, rng=jax.random.PRNGKey(5), return_stats=True)
    assert out.shape == (prompt.shape[0], prompt.shape[1] + new)
    assert int(stats["rounds"]) == -(-(new - 1) // (gamma + 1))  # ceil
    assert float(stats["draft_accept_rate"]) == 1.0
    assert (np.asarray(out) < model.vocab).all() and (np.asarray(out) >= 0).all()


def test_sampled_marginal_matches_generate():
    """Distributional end-to-end check: over a large batch of identical
    prompts, the marginal distribution of each generated position must
    match ancestral sampling's (total variation within Monte-Carlo
    noise), with an imperfect draft forcing real rejections."""
    model = _tiny(vocab=16, d_model=16, n_layers=1, n_heads=2, d_ff=32)
    draft = _tiny(vocab=16, d_model=16, n_layers=1, n_heads=2, d_ff=32)
    params, _ = _params(model, b=1, s=4)
    draft_params, _ = _params(draft, b=1, s=4, seed=123)
    b = 4096
    prompt = jnp.tile(jnp.asarray([[3, 1, 2, 7]], jnp.int32), (b, 1))
    new, t = 3, 1.0
    anc = generate(model, params, prompt, new, temperature=t,
                   rng=jax.random.PRNGKey(11))
    spec = speculative_generate(model, params, draft, draft_params, prompt,
                                new, gamma=2, temperature=t,
                                rng=jax.random.PRNGKey(22))
    for pos in range(new):
        a = np.bincount(np.asarray(anc)[:, 4 + pos], minlength=16) / b
        s = np.bincount(np.asarray(spec)[:, 4 + pos], minlength=16) / b
        tvd = 0.5 * np.abs(a - s).sum()
        assert tvd < 0.05, f"position {pos}: TVD {tvd}"


def test_eos_pins_tail():
    """Once a row emits eos, everything after is eos — including tokens
    committed in the same speculative block."""
    model = _tiny(vocab=8)
    params, _ = _params(model, b=3, s=6)
    prompt = jax.random.randint(jax.random.PRNGKey(2), (3, 6), 0, 8)
    draft = _tiny(vocab=8, n_layers=1)
    draft_params, _ = _params(draft, b=3, s=6, seed=9)
    out = np.asarray(speculative_generate(
        model, params, draft, draft_params, prompt, 16, gamma=3, eos_id=5))
    for row in out:
        gen = row[6:]
        hits = np.nonzero(gen == 5)[0]
        if hits.size:
            assert (gen[hits[0]:] == 5).all()
    # And greedy-with-eos still matches ancestral greedy-with-eos.
    want = np.asarray(generate(model, params, prompt, 16, eos_id=5))
    np.testing.assert_array_equal(out, want)


def test_gqa_window_draft_composes():
    """Speculative decode composes with the GQA + sliding-window cache
    variants (the decode block step handles both)."""
    model = _tiny(n_kv_heads=2, attn_window=8)
    params, prompt = _params(model)
    draft = _tiny(n_layers=1, n_kv_heads=2, attn_window=8)
    draft_params, _ = _params(draft, seed=3)
    want = generate(model, params, prompt, 10)
    got = speculative_generate(model, params, draft, draft_params, prompt,
                               10, gamma=3)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("chunk", [4, 5, 12, 100])
def test_chunked_prefill_parity(chunk):
    """prefill_chunk re-blocks the same computation: bitwise-equal output
    for dividing chunks (4 and 12 — both end the scan on rem == 0), a
    non-dividing chunk (5, remainder block), and an oversized chunk (100
    >= p, the unchunked fast path), on both generators, incl. a
    GQA+window model."""
    model = _tiny(n_kv_heads=2, attn_window=10)
    params, prompt = _params(model)  # p = 24
    draft = _tiny(n_layers=1, n_kv_heads=2, attn_window=10)
    draft_params, _ = _params(draft, seed=3)

    want = generate(model, params, prompt, 8)
    got = generate(model, params, prompt, 8, prefill_chunk=chunk)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    want_s = generate(model, params, prompt, 8, temperature=0.7,
                      rng=jax.random.PRNGKey(4))
    got_s = generate(model, params, prompt, 8, temperature=0.7,
                     rng=jax.random.PRNGKey(4), prefill_chunk=chunk)
    np.testing.assert_array_equal(np.asarray(got_s), np.asarray(want_s))

    want_sp = speculative_generate(model, params, draft, draft_params,
                                   prompt, 8, gamma=2)
    got_sp = speculative_generate(model, params, draft, draft_params,
                                  prompt, 8, gamma=2, prefill_chunk=chunk)
    np.testing.assert_array_equal(np.asarray(got_sp), np.asarray(want_sp))


@pytest.mark.parametrize("prompt_len", [128, 5], ids=["tiles", "no_tile"])
def test_flash_prefill_matches_reference_prefill(prompt_len):
    """attn_impl="flash" routes the empty-cache prefill through the Pallas
    kernel (interpreted on CPU); generation must agree with the reference-
    impl model token-for-token at a tileable prompt length — the two
    prefills differ only in attention blocking — and at one no tile
    divides, where flash_attention's own fallback is the reference einsum:
    a flash model decodes from a prompt of any length."""
    ref = _tiny(n_kv_heads=2)
    fla = _tiny(n_kv_heads=2, attn_impl="flash")
    params, _ = _params(ref, s=128)
    prompt = jax.random.randint(jax.random.PRNGKey(0), (2, prompt_len), 0, 64)
    want = generate(ref, params, prompt, 6)
    got = generate(fla, params, prompt, 6)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_prefill_mode_poisons_on_nonempty_cache():
    """prefill=True is an empty-cache contract: applying a prefill clone
    to a cache mid-stream computes block-only attention that ignores the
    committed context — poisoned to NaN, same discipline as overflow."""
    from tpunet.models import init_cache

    model = _tiny()
    params, toks = _params(model)
    pm = model.clone(decode=True, prefill=True)
    cache = init_cache(model, 2, 40)
    _, mut = pm.apply({"params": params, "cache": cache}, toks,
                      mutable=["cache"])  # idx 0: fine
    logits, _ = pm.apply({"params": params, "cache": mut["cache"]},
                         toks[:, :4], mutable=["cache"])  # idx 24: poisoned
    assert np.isnan(np.asarray(logits)).all()


def test_prefill_chunk_validation():
    model = _tiny()
    params, prompt = _params(model)
    with pytest.raises(ValueError, match="prefill_chunk"):
        generate(model, params, prompt, 4, prefill_chunk=0)


def test_validation_errors():
    model = _tiny()
    params, prompt = _params(model)
    with pytest.raises(ValueError, match="gamma"):
        speculative_generate(model, params, model, params, prompt, 4, gamma=0)
    with pytest.raises(ValueError, match="max_new_tokens"):
        speculative_generate(model, params, model, params, prompt, 0)
    with pytest.raises(ValueError, match="top_k"):
        speculative_generate(model, params, model, params, prompt, 4, top_k=3)


def test_filtered_logits_shared_helper():
    """generate() and speculative_generate() must sample through the SAME
    filter chain — pin the helper's semantics: top-k keeps exactly k,
    top-p keeps the smallest prefix reaching p, composed k-then-p."""
    logits = jnp.asarray([[2.0, 1.0, 0.5, 0.0, -1.0]])
    out = filtered_logits(logits, 1.0, 3, None)
    assert (np.asarray(out[0]) == -np.inf).sum() == 2
    out = filtered_logits(logits, 1.0, None, 0.6)
    keep = np.isfinite(np.asarray(out[0]))
    probs = np.asarray(jax.nn.softmax(logits[0]))
    order = np.argsort(-probs)
    cum = 0.0
    expect = np.zeros(5, bool)
    for i in order:
        expect[i] = True
        cum += probs[i]
        if cum >= 0.6:
            break
    np.testing.assert_array_equal(keep, expect)


def test_all_inference_features_compose_greedy_exact():
    """The whole inference feature matrix in ONE configuration: GQA x
    sliding window x chunked prefill x speculative decoding with an int8
    quantized self-draft - greedy output must still be bitwise the plain
    fp generate()'s."""
    from tpunet.models import quantize_params

    model = _tiny(n_kv_heads=2, attn_window=12)
    params, prompt = _params(model)
    qdraft = model.clone(weight_quant="int8")
    qp = quantize_params(params)
    want = generate(model, params, prompt, 10)
    got = speculative_generate(
        model, params, qdraft, qp, prompt, 10, gamma=3, prefill_chunk=7)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_per_row_speculative_bitwise_and_fewer_rounds():
    """per_row=True: every row commits its OWN accepted prefix - output
    still bitwise generate()'s, and (greedy being deterministic) the
    round count can only improve on lockstep (lockstep progress per round
    is the batch min, per-row progress is each row's own)."""
    model = _tiny()
    params, _ = _params(model, b=3)
    prompt = jax.random.randint(jax.random.PRNGKey(4), (3, 24), 0, 64)
    draft = _tiny(n_layers=1)
    draft_params, _ = _params(draft, seed=7)
    want = generate(model, params, prompt, 14)
    got_ls, st_ls = speculative_generate(
        model, params, draft, draft_params, prompt, 14, gamma=3,
        return_stats=True)
    got_pr, st_pr = speculative_generate(
        model, params, draft, draft_params, prompt, 14, gamma=3,
        per_row=True, return_stats=True)
    np.testing.assert_array_equal(np.asarray(got_ls), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got_pr), np.asarray(want))
    assert int(st_pr["rounds"]) <= int(st_ls["rounds"])
    assert 0.0 <= float(st_pr["draft_accept_rate"]) <= 1.0


def test_per_row_speculative_eos_and_sampling():
    """per_row composes with eos pinning (bitwise vs the eos oracle in
    greedy) and runs in sampling mode with in-vocab output."""
    model = _tiny(vocab=8)
    params, _ = _params(model, b=3, s=6)
    prompt = jax.random.randint(jax.random.PRNGKey(2), (3, 6), 0, 8)
    draft = _tiny(vocab=8, n_layers=1)
    draft_params, _ = _params(draft, b=3, s=6, seed=9)
    want = np.asarray(generate(model, params, prompt, 12, eos_id=5))
    got = np.asarray(speculative_generate(
        model, params, draft, draft_params, prompt, 12, gamma=3,
        eos_id=5, per_row=True))
    np.testing.assert_array_equal(got, want)

    out = speculative_generate(
        model, params, draft, draft_params, prompt, 9, gamma=2,
        temperature=0.8, per_row=True, rng=jax.random.PRNGKey(3))
    o = np.asarray(out)
    assert o.shape == (3, 15) and ((o >= 0) & (o < 8)).all()


def test_per_row_speculative_with_quant_draft_and_chunked_prefill():
    """per_row x int8 self-draft x chunked prefill: still bitwise."""
    from tpunet.models import quantize_params

    model = _tiny(n_kv_heads=2)
    params, prompt = _params(model)
    qdraft = model.clone(weight_quant="int8")
    qp = quantize_params(params)
    want = generate(model, params, prompt, 10)
    got = speculative_generate(model, params, qdraft, qp, prompt, 10,
                               gamma=3, per_row=True, prefill_chunk=7)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# --- speculative x rolling-window ring cache (round 5) --------------------
# With gamma + 1 <= window, speculation runs on the RING cache: the round
# stashes the slots it overwrites and restores the rejected span
# (_spec_ring_stash/_spec_ring_restore). Oracle: the identical model with
# decode_ring_cache=False (full-capacity masked cache, round-4 rollback).


def _ring_pair(window=8, **kw):
    model = _tiny(n_kv_heads=2, attn_window=window, **kw)
    draft = _tiny(n_layers=1, n_kv_heads=2, attn_window=window, **kw)
    params, _ = _params(model)
    dparams, _ = _params(draft, seed=3)
    return model, draft, params, dparams


def test_spec_ring_cache_matches_masked_cache_greedy():
    model, draft, params, dparams = _ring_pair()
    prompt = jax.random.randint(jax.random.PRNGKey(5), (2, 6), 0, 64)
    kw = dict(max_new_tokens=12, gamma=3, temperature=0.0)
    ring = speculative_generate(model, params, draft, dparams, prompt, **kw)
    masked = speculative_generate(
        model.clone(decode_ring_cache=False), params,
        draft.clone(decode_ring_cache=False), dparams, prompt, **kw)
    assert jnp.array_equal(ring, masked)


def test_spec_ring_cache_matches_masked_cache_sampled_per_row():
    model, draft, params, dparams = _ring_pair()
    prompt = jax.random.randint(jax.random.PRNGKey(6), (3, 6), 0, 64)
    for per_row in (False, True):
        kw = dict(max_new_tokens=12, gamma=3, temperature=0.9, top_k=8,
                  rng=jax.random.PRNGKey(11), per_row=per_row)
        ring = speculative_generate(model, params, draft, dparams, prompt,
                                    **kw)
        masked = speculative_generate(
            model.clone(decode_ring_cache=False), params,
            draft.clone(decode_ring_cache=False), dparams, prompt, **kw)
        assert jnp.array_equal(ring, masked), f"per_row={per_row}"


def test_spec_ring_cache_matches_plain_generate():
    # End-to-end exactness: ring-cache speculation == plain generate()
    # greedy (the strongest oracle — no shared code with the spec loop).
    model, draft, params, dparams = _ring_pair()
    prompt = jax.random.randint(jax.random.PRNGKey(7), (2, 6), 0, 64)
    out = speculative_generate(model, params, draft, dparams, prompt,
                               max_new_tokens=10, gamma=2, temperature=0.0)
    ref = generate(model, params, prompt, max_new_tokens=10, temperature=0.0)
    assert jnp.array_equal(out[:, :ref.shape[1]], ref)


def test_spec_narrow_window_falls_back_to_masked_cache():
    # gamma + 1 > window: a round's writes would lap the ring (duplicate
    # slots in the stash scatter) — the masked full-capacity cache is the
    # correct substrate, and results still match plain generate().
    model, draft, params, dparams = _ring_pair(window=4)
    prompt = jax.random.randint(jax.random.PRNGKey(8), (2, 6), 0, 64)
    out = speculative_generate(model, params, draft, dparams, prompt,
                               max_new_tokens=8, gamma=4, temperature=0.0)
    ref = generate(model, params, prompt, max_new_tokens=8, temperature=0.0)
    assert jnp.array_equal(out[:, :ref.shape[1]], ref)
