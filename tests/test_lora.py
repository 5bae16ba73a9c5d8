"""LoRA adapters: init identity, frozen-base training, merge, QLoRA."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tpunet.models import (Transformer, generate, graft_base, lora_mask,
                           lora_optimizer, merge_lora, quantize_params)


def _tiny(**kw):
    kw.setdefault("vocab", 64)
    kw.setdefault("d_model", 32)
    kw.setdefault("n_layers", 2)
    kw.setdefault("n_heads", 4)
    kw.setdefault("d_ff", 64)
    kw.setdefault("compute_dtype", jnp.float32)
    return Transformer(**kw)


def _base(**kw):
    model = _tiny(**kw)
    toks = jax.random.randint(jax.random.PRNGKey(0), (2, 24), 0, model.vocab)
    params = model.init(jax.random.PRNGKey(1), toks)["params"]
    return model, params, toks


def test_grafted_adapter_is_identity_at_init():
    """B = 0 at init, so the grafted adapted model is bitwise the base."""
    base_model, base_params, toks = _base()
    lmodel = base_model.clone(lora_rank=4)
    linit = lmodel.init(jax.random.PRNGKey(2), toks)["params"]
    lparams = graft_base(linit, base_params)
    want = base_model.apply({"params": base_params}, toks)
    got = lmodel.apply({"params": lparams}, toks)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # The adapter params exist where they should.
    attn_q = lparams["block0"]["attn"]["q"]
    assert set(attn_q) == {"base", "lora_a", "lora_b"}
    assert attn_q["lora_b"].shape == (4, 32)
    assert (np.asarray(attn_q["lora_b"]) == 0).all()


def test_masked_training_moves_only_adapters():
    """lora_optimizer (tx on adapters, set_to_zero elsewhere — NOT bare
    optax.masked, which would pass raw gradients through to the "frozen"
    base): loss drops while every base leaf (and embed/norms) stays
    bitwise frozen."""
    base_model, base_params, toks = _base()
    lmodel = base_model.clone(lora_rank=4)
    linit = lmodel.init(jax.random.PRNGKey(2), toks)["params"]
    params = graft_base(linit, base_params)
    mask = lora_mask(params)
    assert mask["block0"]["attn"]["q"]["lora_a"] is True
    assert mask["block0"]["attn"]["q"]["base"]["kernel"] is False
    assert mask["embed"] is False

    tx = lora_optimizer(optax.adam(5e-3), params)
    opt_state = tx.init(params)
    labels = jnp.roll(toks, -1, axis=1)

    def loss_fn(p):
        logits = lmodel.apply({"params": p}, toks)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean()

    @jax.jit
    def step(p, s):
        loss, g = jax.value_and_grad(loss_fn)(p)
        updates, s = tx.update(g, s, p)
        return optax.apply_updates(p, updates), s, loss

    first = None
    for _ in range(30):
        params, opt_state, loss = step(params, opt_state)
        first = float(loss) if first is None else first
    assert float(loss) < first  # adapters learned something
    np.testing.assert_array_equal(
        np.asarray(params["block0"]["attn"]["q"]["base"]["kernel"]),
        np.asarray(base_params["block0"]["attn"]["q"]["kernel"]))
    np.testing.assert_array_equal(np.asarray(params["embed"]),
                                  np.asarray(base_params["embed"]))
    assert not (np.asarray(params["block0"]["attn"]["q"]["lora_b"])
                == 0).all()


def test_merge_lora_folds_exactly():
    """merge_lora produces a PLAIN tree whose outputs match the adapted
    model (fp math: A@B·scale folded into the kernel)."""
    base_model, base_params, toks = _base()
    lmodel = base_model.clone(lora_rank=4)
    linit = lmodel.init(jax.random.PRNGKey(2), toks)["params"]
    params = graft_base(linit, base_params)
    # Give the adapters nonzero content so the merge is non-trivial.
    params = jax.tree.map(lambda leaf, m: leaf + 0.01 if m else leaf,
                          params, lora_mask(params))
    merged = merge_lora(params)
    want = lmodel.apply({"params": params}, toks)
    got = base_model.apply({"params": merged}, toks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=1e-5)
    # And generation with the adapted model works end to end.
    out = generate(lmodel, params, toks[:, :8], 4)
    assert out.shape == (2, 12)


def test_qlora_int8_base_fp_adapters():
    """weight_quant + lora_rank: int8 frozen base with fp adapters —
    grafts from quantize_params, is near the quant base at init (B = 0,
    exact), and merge is refused (int8 can't absorb the delta)."""
    base_model, base_params, toks = _base()
    qmodel = base_model.clone(weight_quant="int8", lora_rank=4)
    qinit = qmodel.init(jax.random.PRNGKey(2), toks)["params"]
    qparams = graft_base(qinit, quantize_params(base_params))
    node = qparams["block0"]["attn"]["q"]
    assert set(node) == {"base", "lora_a", "lora_b"}
    assert set(node["base"]) == {"q", "scale"}
    want = base_model.clone(weight_quant="int8").apply(
        {"params": quantize_params(base_params)}, toks)
    got = qmodel.apply({"params": qparams}, toks)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    with pytest.raises(ValueError, match="fp base"):
        merge_lora(qparams)


def test_lora_tp_rules_shard_the_adapted_tree():
    """transformer_partition_rules must reach through the 'base' nesting
    and shard the adapters by the Megatron LoRA convention (A replicated /
    B output-sharded for column-parallel; transposed for row-parallel) —
    and a dp x mdl sharded forward matches the single-replica one."""
    from tpunet.models import transformer_partition_rules
    from tpunet.parallel import make_named_mesh, shard_params

    base_model, base_params, toks = _base(n_kv_heads=2)
    lmodel = base_model.clone(lora_rank=4)
    linit = lmodel.init(jax.random.PRNGKey(2), toks)["params"]
    params = graft_base(linit, base_params)
    params = jax.tree.map(lambda leaf, m: leaf + 0.01 if m else leaf,
                          params, lora_mask(params))

    mesh = make_named_mesh({"dp": 2, "mdl": 2})
    rules = transformer_partition_rules(tp_axis="mdl")
    sh = shard_params(params, mesh, rules)
    P = jax.sharding.PartitionSpec
    attn_q = sh["block0"]["attn"]["q"]
    assert attn_q["base"]["kernel"].spec == P(None, "mdl")
    assert attn_q["lora_a"].spec == P()
    assert attn_q["lora_b"].spec == P(None, "mdl")
    out = sh["block0"]["attn"]["out"]
    assert out["base"]["kernel"].spec == P("mdl", None)
    assert out["lora_a"].spec == P("mdl", None)
    assert out["lora_b"].spec == P()

    expected = lmodel.apply({"params": params}, toks)
    params_sh = jax.device_put(params, sh)
    with mesh:
        got = jax.jit(lambda p, t: lmodel.apply({"params": p}, t))(
            params_sh, jnp.asarray(toks))
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               atol=1e-4, rtol=1e-4)


def test_lora_features_only_guard():
    lmodel = _tiny(lora_rank=4)
    _, params, toks = _base()
    with pytest.raises(ValueError, match="lora_rank"):
        lmodel.apply({"params": params}, toks, features_only=True)


def test_lora_with_fit_and_checkpoint(tmp_path):
    """The PEFT workflow through the framework's own driver: graft a base,
    fit() with lora_optimizer (checkpoint cadence on the ADAPTED tree),
    resume exactly, and the base stays frozen through it all."""
    from tpunet.train import TrainState, fit, make_train_step

    base_model, base_params, toks = _base()
    lmodel = base_model.clone(lora_rank=4)
    linit = lmodel.init(jax.random.PRNGKey(2), toks)["params"]
    params = graft_base(linit, base_params)
    # make_train_step donates the state, and graft_base shares leaves with
    # base_params - snapshot the frozen reference to host BEFORE fitting.
    base_q_kernel = np.asarray(base_params["block0"]["attn"]["q"]["kernel"])
    tx = lora_optimizer(optax.adam(5e-3), params)
    state = TrainState(step=jnp.zeros((), jnp.int32), params=params,
                       opt_state=tx.init(params))
    step = make_train_step(lmodel, tx)

    labels = jnp.roll(toks, -1, axis=1)

    def batches():
        while True:
            yield toks, labels

    ckpt = str(tmp_path / "ckpt")
    state = fit(state, step, batches(), steps=12, checkpoint_dir=ckpt,
                checkpoint_every=6)
    np.testing.assert_array_equal(
        np.asarray(state.params["block0"]["attn"]["q"]["base"]["kernel"]),
        base_q_kernel)
    trained_b = np.asarray(state.params["block0"]["attn"]["q"]["lora_b"])
    assert not (trained_b == 0).all()

    # Resume from the checkpoint into a fresh state skeleton (a NEW init:
    # the first fit donated the old leaves): the adapted (nested) tree
    # round-trips through orbax and training continues.
    skel = lmodel.init(jax.random.PRNGKey(3), toks)["params"]
    fresh = TrainState(step=jnp.zeros((), jnp.int32), params=skel,
                       opt_state=tx.init(skel))
    resumed = fit(fresh, step, batches(), steps=12, checkpoint_dir=ckpt)
    np.testing.assert_array_equal(
        np.asarray(resumed.params["block0"]["attn"]["q"]["lora_b"]),
        trained_b)


def test_qlora_training_step_with_float0():
    """The QLoRA gradient/apply path: allow_int gives float0 grads for the
    int8 base; lora_apply_updates leaves those leaves alone while the
    adapters move (plain optax.apply_updates would crash on float0)."""
    from tpunet.models import lora_apply_updates

    base_model, base_params, toks = _base()
    qlmodel = base_model.clone(weight_quant="int8", lora_rank=4)
    qinit = qlmodel.init(jax.random.PRNGKey(2), toks)["params"]
    params = graft_base(qinit, quantize_params(base_params))
    base_q = np.asarray(params["block0"]["attn"]["q"]["base"]["q"])
    tx = lora_optimizer(optax.adam(1e-2), params)
    opt_state = tx.init(params)
    labels = jnp.roll(toks, -1, axis=1)

    def loss_fn(p):
        logits = qlmodel.apply({"params": p}, toks)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean()

    for _ in range(5):
        loss, g = jax.value_and_grad(loss_fn, allow_int=True)(params)
        updates, opt_state = tx.update(g, opt_state, params)
        params = lora_apply_updates(params, updates)
    node = params["block0"]["attn"]["q"]
    np.testing.assert_array_equal(np.asarray(node["base"]["q"]), base_q)
    assert node["base"]["q"].dtype == jnp.int8
    assert not (np.asarray(node["lora_b"]) == 0).all()


def test_qlora_trains_through_fit():
    """QLoRA through the standard driver: make_train_step differentiates a
    tree containing frozen int8 leaves (allow_int -> float0) and applies
    updates without touching them; fit() runs it. Covers both the single
    backward and the accum_steps scan."""
    from tpunet.train import TrainState, fit, make_train_step

    base_model, base_params, toks = _base()
    qlmodel = base_model.clone(weight_quant="int8", lora_rank=4)
    qinit = qlmodel.init(jax.random.PRNGKey(2), toks)["params"]
    qbase = quantize_params(base_params)
    params = graft_base(qinit, qbase)
    base_q = np.asarray(params["block0"]["attn"]["q"]["base"]["q"])
    tx = lora_optimizer(optax.adam(1e-2), params)
    state = TrainState(step=jnp.zeros((), jnp.int32), params=params,
                       opt_state=tx.init(params))
    labels = jnp.roll(toks, -1, axis=1)

    def batches():
        while True:
            yield toks, labels

    for accum in (None, 2):
        step = make_train_step(qlmodel, tx, accum_steps=accum)
        state = fit(state, step, batches(), steps=int(state.step) + 4)
        node = state.params["block0"]["attn"]["q"]
        np.testing.assert_array_equal(np.asarray(node["base"]["q"]), base_q)
        assert node["base"]["q"].dtype == jnp.int8
        assert not (np.asarray(node["lora_b"]) == 0).all()


def _qlora_cross_host_worker(rank: int, world: int, port: int, q) -> None:
    # QLoRA + cross_host: gradients contain float0 leaves
    # (frozen int8 base under allow_int) which the DCN tier must pass
    # through — both the single-vector ravel path and the bucketed path
    # used to crash at trace time on ravel/concatenate of float0.
    try:
        import jax

        jax.config.update("jax_platforms", "cpu")
        import jax.numpy as jnp
        import numpy as np
        import optax

        from tpunet import distributed
        from tpunet.models import (Transformer, graft_base, lora_optimizer,
                                   quantize_params)
        from tpunet.train import TrainState, make_train_step

        distributed.initialize(f"127.0.0.1:{port}", rank, world)
        base_model = Transformer(vocab=32, d_model=16, n_layers=1, n_heads=2,
                                 d_ff=32, compute_dtype=jnp.float32)
        toks = jax.random.randint(jax.random.PRNGKey(10 + rank), (2, 8), 0, 32)
        labels = jnp.roll(toks, -1, axis=1)
        base_params = base_model.init(jax.random.PRNGKey(0), toks)["params"]
        qmodel = base_model.clone(weight_quant="int8", lora_rank=4)
        qinit = qmodel.init(jax.random.PRNGKey(2), toks)["params"]
        params = graft_base(qinit, quantize_params(base_params))
        frozen_q = np.asarray(params["block0"]["attn"]["q"]["base"]["q"])
        tx = lora_optimizer(optax.adam(5e-3), params)
        state = TrainState(step=jnp.zeros((), jnp.int32), params=params,
                           opt_state=tx.init(params))
        for bucket_bytes in (None, 1 << 10):
            step = make_train_step(qmodel, tx, cross_host=True, donate=False,
                                   bucket_bytes=bucket_bytes)
            s = state
            losses = []
            for i in range(3):
                s, loss = step(s, toks, labels, jax.random.PRNGKey(i))
                losses.append(float(loss))
            assert all(np.isfinite(l) for l in losses), (bucket_bytes, losses)
            assert losses[-1] < losses[0], (bucket_bytes, losses)
            # Frozen int8 base must be bit-identical after training.
            np.testing.assert_array_equal(
                np.asarray(s.params["block0"]["attn"]["q"]["base"]["q"]),
                frozen_q)
            # Adapters must be identical across ranks (coupled by the
            # reduced gradient).
            from jax.flatten_util import ravel_pytree

            from tpunet.interop import dcn_all_gather

            flat = ravel_pytree(
                [s.params["block0"]["attn"]["q"]["lora_a"],
                 s.params["block0"]["attn"]["q"]["lora_b"]])[0]
            gathered = np.asarray(jax.jit(dcn_all_gather)(flat))
            for r in range(1, world):
                np.testing.assert_array_equal(gathered[0], gathered[r])
        distributed.finalize()
        q.put((rank, "OK"))
    except Exception as e:  # noqa: BLE001
        q.put((rank, f"FAIL: {type(e).__name__}: {e}"))


def test_qlora_cross_host_training_2proc():
    from conftest import run_spawn_workers

    run_spawn_workers(_qlora_cross_host_worker, 2)
