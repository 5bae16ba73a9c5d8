"""Continuous batching: per-row cache parity and the slot server.

Ground truth for every server output is single-sequence `generate()` on
the same prompt with the same params — a slot's tokens must not depend on
what the other slots are doing (different lengths, refills, garbage
decoding in idle rows).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpunet.models import BatchServer, Transformer, generate


def _tiny(**kw):
    kw.setdefault("vocab", 64)
    kw.setdefault("d_model", 32)
    kw.setdefault("n_layers", 2)
    kw.setdefault("n_heads", 4)
    kw.setdefault("d_ff", 64)
    kw.setdefault("compute_dtype", jnp.float32)
    return Transformer(**kw)


def _setup(**kw):
    model = _tiny(**kw)
    toks = jax.random.randint(jax.random.PRNGKey(0), (2, 24), 0, model.vocab)
    params = model.init(jax.random.PRNGKey(1), toks)["params"]
    return model, params


def _oracle(model, params, prompt, n, **kw):
    out = generate(model, params, jnp.asarray(prompt)[None], n, **kw)
    return np.asarray(out)[0, len(prompt):]


def test_per_row_cache_matches_scalar_when_aligned():
    """With every row at the same offset, the per-row path is the scalar
    path with a broadcast index — same cache contents, same logits."""
    from tpunet.models.generate import init_cache

    model, params = _setup()
    toks = jax.random.randint(jax.random.PRNGKey(2), (3, 10), 0, 64)
    scalar = model.clone(decode=True)
    perrow = model.clone(decode=True, per_row_cache=True)
    c1 = init_cache(scalar, 3, 16)
    c2 = init_cache(perrow, 3, 16)
    l1, m1 = scalar.apply({"params": params, "cache": c1}, toks,
                          mutable=["cache"])
    l2, m2 = perrow.apply({"params": params, "cache": c2}, toks,
                          mutable=["cache"])
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))
    k1 = m1["cache"]["block0"]["attn"]["cached_key"]
    k2 = m2["cache"]["block0"]["attn"]["cached_key"]
    np.testing.assert_array_equal(np.asarray(k1), np.asarray(k2))
    assert m2["cache"]["block0"]["attn"]["cache_index"].shape == (3,)


@pytest.mark.parametrize("steps_per_call", [1, 4, 16])
def test_server_matches_generate_mixed_lengths(steps_per_call):
    """Slots running DIFFERENT prompt lengths concurrently each reproduce
    their own single-sequence generate() output — at every window size
    (steps_per_call coarsens scheduling granularity, never tokens)."""
    model, params = _setup(n_kv_heads=2)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 64, n).astype(np.int32)
               for n in (5, 9, 13)]
    srv = BatchServer(model, params, slots=3, max_len=40,
                      steps_per_call=steps_per_call)
    ids = [srv.submit(p, 8) for p in prompts]
    results = srv.run()
    assert sorted(results) == sorted(ids)
    for p, i in zip(prompts, ids):
        np.testing.assert_array_equal(results[i], _oracle(model, params, p, 8))


def test_server_slot_refill_more_requests_than_slots():
    """6 requests through 2 slots: refills reuse dead rows (stale K/V
    above the new frontier, stale index reset) and every output still
    matches its oracle."""
    model, params = _setup()
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 64, 4 + (i % 3)).astype(np.int32)
               for i in range(6)]
    lens = [6, 3, 9, 4, 7, 5]
    srv = BatchServer(model, params, slots=2, max_len=24)
    ids = [srv.submit(p, n) for p, n in zip(prompts, lens)]
    results = srv.run()
    assert sorted(results) == sorted(ids)
    for p, n, i in zip(prompts, lens, ids):
        np.testing.assert_array_equal(results[i], _oracle(model, params, p, n))


def test_server_eos_frees_slot_early():
    """A request hitting eos retires immediately (possibly at its very
    first, prefill-sampled token) and its output matches the eos-pinned
    oracle up to its own length."""
    model, params = _setup()
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 64, 6).astype(np.int32) for _ in range(4)]
    eos = 7
    srv = BatchServer(model, params, slots=2, max_len=24, eos_id=eos)
    ids = [srv.submit(p, 10) for p in prompts]
    results = srv.run()
    for p, i in zip(prompts, ids):
        want = _oracle(model, params, p, 10, eos_id=eos)
        got = results[i]
        assert len(got) <= 10
        np.testing.assert_array_equal(got, want[:len(got)])
        if len(got) < 10:
            assert got[-1] == eos  # early retirement only ever at eos


def test_server_sampled_rows_are_independent():
    """Sampling mode smoke: outputs are in-vocab and each request
    completes at its requested length."""
    model, params = _setup()
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 64, 5).astype(np.int32) for _ in range(3)]
    srv = BatchServer(model, params, slots=2, max_len=24, temperature=0.9,
                      top_k=8, rng=jax.random.PRNGKey(9))
    ids = [srv.submit(p, 6) for p in prompts]
    results = srv.run()
    for i in ids:
        assert results[i].shape == (6,)
        assert ((results[i] >= 0) & (results[i] < 64)).all()


def test_run_returns_requests_finished_at_prefill():
    """max_new=1 retires during submit()'s prefill; run() must still
    return it (the done buffer drains even with nothing live)."""
    model, params = _setup()
    p = np.random.default_rng(5).integers(0, 64, 6).astype(np.int32)
    srv = BatchServer(model, params, slots=1, max_len=16)
    rid = srv.submit(p, 1)
    results = srv.run()
    np.testing.assert_array_equal(results[rid], _oracle(model, params, p, 1))


def test_serve_bench_cli(capsys):
    # --reps 1: the median/IQR code path is identical at any reps;
    # 7 interleaved passes would add CI time with no assertion power.
    from benchmarks.serve_bench import main as bench_main

    bench_main(["--platform", "cpu",
                "--requests", "4", "--slots", "2", "--prompt", "8",
                "--new-min", "2", "--new-max", "6", "--steps-per-call", "4",
                "--d", "32", "--layers", "1", "--heads", "2", "--ff", "64",
                "--vocab", "64", "--reps", "1"])
    import json

    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["serve_tok_s"] > 0 and out["lockstep_tok_s"] > 0
    assert (out["platform"], out["device_count"]) == ("cpu", 8)
    assert out["serve_micro_steps"] > 0
    assert out["sched_win"] > 0


def test_server_validation():
    model, params = _setup()
    srv = BatchServer(model, params, slots=1, max_len=16)
    with pytest.raises(ValueError, match="max_len"):
        srv.submit(np.zeros(10, np.int32), 10)
    with pytest.raises(ValueError, match="1-D"):
        srv.submit(np.zeros((2, 3), np.int32), 2)
    with pytest.raises(ValueError, match="slots"):
        BatchServer(model, params, slots=0, max_len=16)
    with pytest.raises(ValueError, match="dense model"):
        BatchServer(_tiny(n_experts=2), params, slots=1, max_len=16)


def test_server_composes_with_quant_and_window():
    """BatchServer x int8 weights x GQA x sliding window: each slot still
    reproduces its own single-sequence quantized generate()."""
    from tpunet.models import quantize_params

    model = _tiny(n_kv_heads=2, attn_window=10, weight_quant="int8")
    _, params = _setup(n_kv_heads=2, attn_window=10)
    qp = quantize_params(params)
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, 64, n).astype(np.int32) for n in (6, 11)]
    srv = BatchServer(model, qp, slots=2, max_len=32, steps_per_call=4)
    ids = [srv.submit(p, 7) for p in prompts]
    results = srv.run()
    for p, i in zip(prompts, ids):
        np.testing.assert_array_equal(results[i], _oracle(model, qp, p, 7))


def test_text_in_text_out_end_to_end(tmp_path):
    """The whole stack on raw text: ByteTokenizer -> pack_documents ->
    TokenDataset -> fit() (loss drops) -> BatchServer serves a learned
    byte continuation of a repeating corpus."""
    import optax

    from tpunet.data import ByteTokenizer, TokenDataset, pack_documents
    from tpunet.train import create_train_state, fit, make_train_step

    tok = ByteTokenizer()
    path = str(tmp_path / "corpus.bin")
    pack_documents([tok.encode("abcdefgh" * 200)], path, vocab=tok.vocab)
    ds = TokenDataset(path, seq=16, vocab=tok.vocab)
    model = _tiny(vocab=tok.vocab, d_model=48)
    inputs, _ = ds.batch(np.arange(4))
    state, _ = create_train_state(
        model, jax.random.PRNGKey(0), jnp.asarray(inputs), optax.adam(3e-3))
    step = make_train_step(model, optax.adam(3e-3))

    def batches():
        rng = np.random.default_rng(0)
        while True:
            x, y = ds.batch(rng.choice(ds.n_windows, 4))
            yield jnp.asarray(x), jnp.asarray(y)

    losses = []
    state = fit(state, step, batches(), steps=150,
                log_every=150, log_fn=lambda rec: losses.append(rec))
    assert losses and losses[-1]["loss"] < 0.6  # learned the cycle

    srv = BatchServer(model, state.params, slots=2, max_len=40)
    rid = srv.submit(tok.encode("abcdefghabc"), 8)
    out = srv.run()[rid]
    assert tok.decode(out) == "defghabc"  # exact byte continuation


def test_run_pipeline_and_coalesce_match_default():
    # pipeline>=2 (in-flight windows + dispatch-time occupancy snapshots +
    # deferred prefill tokens) and refill_coalesce>1 (held refills) must
    # not change greedy outputs — each request's tokens depend only on its
    # own prefix. This is the parity the chip serve step (pipeline=2)
    # leans on.
    model, params = _setup()
    prompts = [np.arange(1, 7 + i) % 50 for i in range(5)]
    news = [3, 9, 5, 12, 1]

    def serve(pipeline, coalesce):
        srv = BatchServer(model, params, slots=2, max_len=24,
                          temperature=0.0, steps_per_call=4,
                          refill_coalesce=coalesce)
        ids = [srv.submit(p, n) for p, n in zip(prompts, news)]
        res = srv.run(pipeline=pipeline)
        return [res[i].tolist() for i in ids]

    base = serve(1, 1)
    assert serve(2, 1) == base
    assert serve(3, 1) == base
    assert serve(1, 2) == base
    assert serve(2, 2) == base


def test_run_pipeline_with_eos_matches_default():
    model, params = _setup()
    eos = 7
    prompts = [np.arange(2, 8), np.arange(3, 9), np.arange(1, 7)]

    def serve(pipeline):
        srv = BatchServer(model, params, slots=2, max_len=30,
                          temperature=0.0, steps_per_call=4, eos_id=eos,
                          refill_coalesce=pipeline)  # exercise both knobs
        ids = [srv.submit(p, 12) for p in prompts]
        res = srv.run(pipeline=pipeline)
        return [res[i].tolist() for i in ids]

    base = serve(1)
    out2 = serve(2)
    assert out2 == base
    for toks in base:
        assert eos not in toks[:-1]  # nothing after a (possible) eos


# --- speculative continuous batching (round 5) ----------------------------
# BatchServer(draft_model=...) turns each decode window into speculative
# rounds: draft gamma, verify in one target forward, commit each row's OWN
# accepted prefix. Exactness oracle: greedy tokens must equal generate()'s
# per request, whatever the draft proposes.


def _spec_srv(model, params, draft, dparams, reqs, **kw):
    srv = BatchServer(model, params, draft_model=draft, draft_params=dparams,
                      **kw)
    ids = [srv.submit(p, n) for p, n in reqs]
    return srv, ids, srv.run()


@pytest.mark.parametrize("steps_per_call,pipeline", [
    (1, 1),
    # Multi-round windows exercise the per-round absorb loop and the
    # mid-window retirement break; pipeline=2 exercises in-flight
    # speculative windows + deferred refill tokens + the dispatch-time
    # occupancy snapshot discarding recycled rows' rounds.
    (4, 1),
    (2, 2),
])
def test_spec_server_greedy_matches_generate_mixed_lengths(
        steps_per_call, pipeline):
    model, params = _setup()
    draft = _tiny(n_layers=1)
    dparams = draft.init(jax.random.PRNGKey(9),
                         jnp.zeros((1, 8), jnp.int32))["params"]
    rng = np.random.default_rng(0)
    reqs = [(rng.integers(0, 50, 5 + i % 3).astype(np.int32), n)
            for i, n in enumerate([4, 11, 6, 13, 3, 8])]
    srv = BatchServer(model, params, draft_model=draft,
                      draft_params=dparams, slots=2, max_len=24,
                      temperature=0.0, gamma=3,
                      steps_per_call=steps_per_call)
    ids = [srv.submit(p, n) for p, n in reqs]
    res = srv.run(pipeline=pipeline)
    for rid, (p, n) in zip(ids, reqs):
        np.testing.assert_array_equal(
            np.asarray(res[rid]), _oracle(model, params, p, n))
    assert srv.stats["spec_rounds"] > 0


def test_spec_server_windowed_ring_matches_generate():
    # Windowed target + draft: the server speculates on the ROLLING RING
    # cache (gamma + 1 <= window) with per-round stash/restore, and the
    # greedy outputs still match generate() exactly.
    model, params = _setup(attn_window=8)
    draft = _tiny(n_layers=1, attn_window=8)
    dparams = draft.init(jax.random.PRNGKey(9),
                         jnp.zeros((1, 8), jnp.int32))["params"]
    rng = np.random.default_rng(1)
    reqs = [(rng.integers(0, 50, 6).astype(np.int32), n)
            for n in [5, 12, 7]]
    srv, ids, res = _spec_srv(model, params, draft, dparams, reqs,
                              slots=2, max_len=24, temperature=0.0, gamma=3)
    for rid, (p, n) in zip(ids, reqs):
        np.testing.assert_array_equal(
            np.asarray(res[rid]), _oracle(model, params, p, n))
    # ring actually backs the server cache
    assert all(leaf.shape[1] == 8 for leaf in jax.tree.leaves(srv._cache)
               if leaf.ndim == 4)


def test_spec_server_quant_self_draft_accepts_and_matches():
    # int8 self-draft: acceptance should be HIGH (the draft agrees with
    # its own fp source), so rounds commit multiple tokens — and outputs
    # stay exactly generate()'s.
    from tpunet.models import quantize_params

    model, params = _setup()
    qmodel = model.clone(weight_quant="int8")
    qparams = quantize_params(params)
    rng = np.random.default_rng(2)
    reqs = [(rng.integers(0, 50, 6).astype(np.int32), 12) for _ in range(3)]
    srv, ids, res = _spec_srv(model, params, qmodel, qparams, reqs,
                              slots=2, max_len=24, temperature=0.0, gamma=4)
    for rid, (p, n) in zip(ids, reqs):
        np.testing.assert_array_equal(
            np.asarray(res[rid]), _oracle(model, params, p, n))
    tok_per_round = (srv.stats["spec_committed"]
                     / max(srv.stats["spec_rounds"], 1))
    assert tok_per_round > 2.0, srv.stats


def test_spec_server_eos_cuts_mid_round():
    model, params = _setup()
    draft = _tiny(n_layers=1)
    dparams = draft.init(jax.random.PRNGKey(9),
                         jnp.zeros((1, 8), jnp.int32))["params"]
    p = np.arange(2, 8).astype(np.int32)
    ref = _oracle(model, params, p, 12)
    eos = int(ref[4])  # force a mid-stream retirement
    first = int(np.nonzero(np.asarray(ref) == eos)[0][0])
    want = list(ref[:first + 1])  # cut at the FIRST occurrence
    srv = BatchServer(model, params, slots=1, max_len=24, temperature=0.0,
                      eos_id=eos, draft_model=draft, draft_params=dparams,
                      gamma=3)
    rid = srv.submit(p, 12)
    res = srv.run()
    assert list(res[rid]) == want


def test_spec_server_sampled_runs_and_validates():
    model, params = _setup()
    draft = _tiny(n_layers=1)
    dparams = draft.init(jax.random.PRNGKey(9),
                         jnp.zeros((1, 8), jnp.int32))["params"]
    srv = BatchServer(model, params, slots=2, max_len=20, temperature=0.8,
                      top_k=8, draft_model=draft, draft_params=dparams,
                      gamma=2)
    ids = [srv.submit(np.arange(1, 7), 8) for _ in range(3)]
    res = srv.run()
    for rid in ids:
        assert res[rid].shape == (8,)
        assert ((res[rid] >= 0) & (res[rid] < model.vocab)).all()
    with pytest.raises(ValueError, match="draft_model and draft_params"):
        BatchServer(model, params, slots=1, max_len=8, draft_model=draft)
    with pytest.raises(ValueError, match="vocab"):
        BatchServer(model, params, slots=1, max_len=8,
                    draft_model=_tiny(vocab=32), draft_params=dparams)
