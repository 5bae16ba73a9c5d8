"""Transformer family tests: forward numerics, TP/SP/EP shardings, training."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from flax.traverse_util import flatten_dict

from tpunet.models import Transformer, transformer_partition_rules
from tpunet.models.transformer import Block, LayerSpec, Mamba2, SelfAttention
from tpunet.parallel import batch_sharding, make_named_mesh, replicated, shard_params
from tpunet.train import TrainState, create_train_state, make_train_step


def _tiny(attn_impl="reference", mesh=None, n_experts=0, **kw):
    return Transformer(
        vocab=64, d_model=32, n_layers=2, n_heads=4, d_ff=64,
        n_experts=n_experts, compute_dtype=jnp.float32,
        attn_impl=attn_impl, mesh=mesh, **kw,
    )


def _tokens(rng, b, s, vocab=64):
    return jax.random.randint(rng, (b, s), 0, vocab)


def test_forward_shapes_dense():
    model = _tiny()
    toks = _tokens(jax.random.PRNGKey(0), 2, 16)
    params = model.init(jax.random.PRNGKey(1), toks)["params"]
    logits = model.apply({"params": params}, toks)
    assert logits.shape == (2, 16, 64)
    assert logits.dtype == jnp.float32
    assert bool(jnp.all(jnp.isfinite(logits)))


@pytest.mark.parametrize("moe_top_k,capacity_factor", [(1, 1.25), (2, 2.0)])
def test_forward_moe_and_aux_loss(moe_top_k, capacity_factor):
    model = _tiny(n_experts=4, moe_every=1, moe_top_k=moe_top_k,
                  capacity_factor=capacity_factor)
    toks = _tokens(jax.random.PRNGKey(0), 2, 16)
    params = model.init(jax.random.PRNGKey(1), toks)["params"]
    logits, state = model.apply({"params": params}, toks, mutable=["intermediates"])
    assert logits.shape == (2, 16, 64)
    assert bool(jnp.all(jnp.isfinite(logits)))
    aux = jax.tree.leaves(state["intermediates"])
    assert len(aux) == 2  # both blocks MoE
    # Switch aux loss is >= 1 at uniform routing, finite always.
    assert all(np.isfinite(float(a)) for a in aux)


def test_causality():
    # Changing a future token must not change earlier logits.
    model = _tiny()
    toks = _tokens(jax.random.PRNGKey(0), 1, 16)
    params = model.init(jax.random.PRNGKey(1), toks)["params"]
    base = model.apply({"params": params}, toks)
    toks2 = toks.at[0, -1].set((toks[0, -1] + 1) % 64)
    pert = model.apply({"params": params}, toks2)
    np.testing.assert_allclose(
        np.asarray(base[0, :-1]), np.asarray(pert[0, :-1]), atol=1e-6
    )
    assert not np.allclose(np.asarray(base[0, -1]), np.asarray(pert[0, -1]))


def test_ring_attn_matches_reference_model():
    mesh = make_named_mesh({"dp": 2, "sp": 4})
    ref_model = _tiny("reference")
    ring_model = _tiny("ring", mesh=mesh)
    toks = _tokens(jax.random.PRNGKey(0), 2, 32)
    params = ref_model.init(jax.random.PRNGKey(1), toks)["params"]
    ref = ref_model.apply({"params": params}, toks)
    ring = ring_model.apply({"params": params}, toks)
    np.testing.assert_allclose(np.asarray(ring), np.asarray(ref), atol=1e-4, rtol=1e-4)


def test_flash_attn_matches_reference_model():
    ref_model = _tiny("reference")
    flash_model = _tiny("flash")
    toks = _tokens(jax.random.PRNGKey(2), 1, 128)
    params = ref_model.init(jax.random.PRNGKey(1), toks)["params"]
    np.testing.assert_allclose(
        np.asarray(flash_model.apply({"params": params}, toks)),
        np.asarray(ref_model.apply({"params": params}, toks)),
        atol=1e-4, rtol=1e-4,
    )


def test_tp_sharded_forward_matches():
    # Megatron TP over mdl: sharded forward == replicated forward.
    mesh = make_named_mesh({"dp": 4, "mdl": 2})
    model = _tiny()
    toks = _tokens(jax.random.PRNGKey(0), 4, 16)
    params = model.init(jax.random.PRNGKey(1), toks)["params"]
    expected = model.apply({"params": params}, toks)

    rules = transformer_partition_rules(tp_axis="mdl")
    shardings = shard_params(params, mesh, rules)
    params_sh = jax.device_put(params, shardings)
    toks_sh = jax.device_put(toks, batch_sharding(mesh))
    with mesh:
        got = jax.jit(lambda p, t: model.apply({"params": p}, t))(params_sh, toks_sh)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected), atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("moe_top_k,capacity_factor", [(1, 1.25), (2, 2.0)])
def test_ep_sharded_moe_forward_matches(moe_top_k, capacity_factor):
    # Expert weights over ep axis; dispatch einsums become all-to-alls.
    mesh = make_named_mesh({"dp": 2, "ep": 4})
    model = _tiny(n_experts=4, moe_every=1, moe_top_k=moe_top_k,
                  capacity_factor=capacity_factor)
    toks = _tokens(jax.random.PRNGKey(0), 2, 16)
    params = model.init(jax.random.PRNGKey(1), toks)["params"]
    expected = model.apply({"params": params}, toks)

    rules = transformer_partition_rules(tp_axis=None, ep_axis="ep")
    shardings = shard_params(params, mesh, rules)
    params_sh = jax.device_put(params, shardings)
    toks_sh = jax.device_put(toks, batch_sharding(mesh))
    with mesh:
        got = jax.jit(lambda p, t: model.apply({"params": p}, t))(params_sh, toks_sh)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected), atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("n_experts", [0, 4])
def test_remat_matches_no_remat(n_experts):
    # Rematerialization must not change values — forward or gradients —
    # including the MoE path (sown aux loss under the lifted remat).
    plain = _tiny(n_experts=n_experts, moe_every=1)
    remat = _tiny(remat=True, n_experts=n_experts, moe_every=1)
    toks = _tokens(jax.random.PRNGKey(0), 2, 16)
    labels = jnp.roll(toks, -1, axis=1)
    params = plain.init(jax.random.PRNGKey(1), toks)["params"]

    np.testing.assert_allclose(
        np.asarray(remat.apply({"params": params}, toks)),
        np.asarray(plain.apply({"params": params}, toks)),
        atol=1e-6,
    )

    if n_experts:
        # The sown moe_aux_loss must survive the lifted remat transform and
        # carry the same values.
        _, ip = plain.apply({"params": params}, toks, mutable=["intermediates"])
        _, ir = remat.apply({"params": params}, toks, mutable=["intermediates"])
        aux_p = sorted(float(a) for a in jax.tree.leaves(ip["intermediates"]))
        aux_r = sorted(float(a) for a in jax.tree.leaves(ir["intermediates"]))
        assert len(aux_r) == len(aux_p) > 0
        np.testing.assert_allclose(aux_r, aux_p, atol=1e-6)

    def loss_fn(model):
        def f(p):
            logits = model.apply({"params": p}, toks)
            return optax.softmax_cross_entropy_with_integer_labels(logits, labels).mean()
        return f

    g_plain = jax.grad(loss_fn(plain))(params)
    g_remat = jax.grad(loss_fn(remat))(params)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-5
        ),
        g_plain, g_remat,
    )


def test_train_step_includes_moe_aux_loss():
    """The Switch balancing term must reach the training loss: the same
    step with a larger moe_aux_weight must report a larger loss."""
    model = _tiny(n_experts=4, moe_every=1)
    tx = optax.adam(1e-2)
    toks = _tokens(jax.random.PRNGKey(0), 4, 16)
    labels = jnp.roll(toks, -1, axis=1)
    state, _ = create_train_state(model, jax.random.PRNGKey(1), toks, tx)
    losses = {}
    for w in (0.0, 10.0):
        step = make_train_step(model, tx, donate=False, moe_aux_weight=w)
        _, loss = step(state, toks, labels, jax.random.PRNGKey(0))
        losses[w] = float(loss)
    # aux loss is e*sum(frac_tokens*frac_probs) >= 1 > 0, so weight 10 must
    # add a visible amount over weight 0.
    assert losses[10.0] > losses[0.0] + 1.0


@pytest.mark.parametrize("n_experts,moe_top_k", [(0, 1), (4, 1), (4, 2)])
def test_train_step_loss_decreases(n_experts, moe_top_k):
    model = _tiny(n_experts=n_experts, moe_top_k=moe_top_k,
                  capacity_factor=2.0 if moe_top_k > 1 else 1.25)
    tx = optax.adam(1e-2)
    toks = _tokens(jax.random.PRNGKey(0), 4, 16)
    labels = jnp.roll(toks, -1, axis=1)
    state, _ = create_train_state(model, jax.random.PRNGKey(1), toks, tx)
    step = make_train_step(model, tx, donate=False)
    losses = []
    s = state
    for i in range(5):
        s, loss = step(s, toks, labels, jax.random.PRNGKey(i))
        losses.append(float(loss))
    assert losses[-1] < losses[0]
    assert all(np.isfinite(l) for l in losses)


def _fields(cls):
    """A flax module's own dataclass fields, by name."""
    return {f.name: f for f in dataclasses.fields(cls)
            if f.name not in ("parent", "name")}


def test_layer_spec_fields_are_transformer_fields():
    """A block-level field is declared on `Transformer` (default, comment)
    and on `LayerSpec` (name, type), and `layer_specs()` copies it by name:
    every spec field but the derived ones (`head_dim`: the model's or
    d_model / n_heads; `rotary`: the layer's place in `attn_pattern`;
    `kind`: its place in `layer_pattern` or `mtp_pattern`) is a model field
    of the same name and type, and has no default of its own to drift from
    the model's. The flash tile is `ops.flash_attention._plan`'s, not an
    option of the model."""
    model = _fields(Transformer)
    for f in dataclasses.fields(LayerSpec):
        assert f.default is dataclasses.MISSING, f.name
        assert f.default_factory is dataclasses.MISSING, f.name
        if f.name not in ("head_dim", "rotary", "kind"):
            assert f.name in model, f.name
            assert f.type == model[f.name].type, f.name
    assert not [n for n in model if n.startswith("flash_block")]
    assert list(_fields(Block)) == ["spec"]
    assert list(_fields(SelfAttention)) == ["spec"]
    assert list(_fields(Mamba2)) == ["spec"]
    spec = Transformer(d_model=96, n_heads=4, rope_theta=5e5).layer_specs()[0]
    assert (spec.head_dim, spec.rope_theta, spec.rotary, spec.kind) == (24, 5e5, True, None)
    for f in dataclasses.fields(LayerSpec):  # the model's defaults, unnamed
        if f.name not in ("head_dim", "rotary", "kind", "n_heads", "rope_theta"):
            assert getattr(spec, f.name) == model[f.name].default, f.name


def test_layer_specs_place_the_experts():
    """`layer_specs()` is where layers differ: experts in every
    `moe_every`-th block, everything else one spec repeated; the
    initialised tree has `moe` exactly where the specs say."""
    model = _tiny(n_experts=4, moe_every=2).clone(n_layers=4)
    specs = model.layer_specs()
    assert [sp.n_experts for sp in specs] == [0, 4, 0, 4]
    assert specs[0] == specs[2] and specs[1] == specs[3]
    assert dataclasses.replace(specs[1], n_experts=0) == specs[0]
    assert len({hash(sp) for sp in specs}) == 2  # hashable: a module field
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    for i, sp in enumerate(specs):
        assert ("moe" in params[f"block{i}"]) == (sp.n_experts > 0), i
        assert ("mlp" in params[f"block{i}"]) == (sp.n_experts == 0), i
    assert {sp.n_experts for sp in _tiny().layer_specs()} == {0}


def test_clone_reaches_every_layers_spec():
    """The contract `generate`, `BatchServer` and `serve/prefill.py` rest
    on: what `model.clone(...)` sets is what every block is built from."""
    model = _tiny(n_kv_heads=2, attn_window=8).clone(n_layers=3)
    assert not any(sp.decode or sp.per_row_cache or sp.weight_quant
                   for sp in model.layer_specs())
    served = model.clone(decode=True, per_row_cache=True, weight_quant="int8")
    specs = served.layer_specs()
    assert len(specs) == 3
    for sp in specs:
        assert (sp.decode, sp.per_row_cache, sp.weight_quant) == (
            True, True, "int8")
        assert (sp.n_kv_heads, sp.attn_window, sp.prefill) == (2, 8, False)


_TOY = dict(vocab=64, d_model=32, n_layers=2, n_heads=4, d_ff=48,
            compute_dtype=jnp.float32)
_ATTN_MHA = {"attn/q/kernel": (32, 32), "attn/k/kernel": (32, 32),
             "attn/v/kernel": (32, 32), "attn/out/kernel": (32, 32)}
_NORMS = {"norm1/scale": (32,), "norm2/scale": (32,)}
_SWIGLU = {"mlp/gate/kernel": (32, 48), "mlp/up/kernel": (32, 48),
           "mlp/down/kernel": (48, 32)}
# (model fields, a block's leaves, the leaves outside the blocks, a block's
# cache leaves at batch 2 and capacity 16). perfbench/weights.py,
# generate._kv_leaves, serve/kv.py, transformer_partition_rules, lora.py and
# quant.py address these by path, and so does every checkpoint.
_TREES = {
    "gelu_mha": (
        {},
        {**_ATTN_MHA, **_NORMS,
         "mlp/up/kernel": (32, 48), "mlp/down/kernel": (48, 32)},
        {"embed": (64, 32), "norm_f/scale": (32,), "lm_head/kernel": (32, 64)},
        {"attn/cached_key": (2, 16, 4, 8), "attn/cached_value": (2, 16, 4, 8),
         "attn/cache_index": ()},
    ),
    "swiglu_gqa_window_flash": (
        dict(mlp_impl="swiglu", n_kv_heads=2, attn_window=8,
             attn_impl="flash"),
        {"attn/q/kernel": (32, 32), "attn/k/kernel": (32, 16),
         "attn/v/kernel": (32, 16), "attn/out/kernel": (32, 32),
         **_NORMS, **_SWIGLU},
        {"embed": (64, 32), "norm_f/scale": (32,), "lm_head/kernel": (32, 64)},
        # the window's ring: min(window, capacity) slots of kv heads
        {"attn/cached_key": (2, 8, 2, 8), "attn/cached_value": (2, 8, 2, 8),
         "attn/cache_index": ()},
    ),
    "eva_heads8_unit_offset_f32_residual": (
        dict(mlp_impl="swiglu", attn_impl="eva", eva_window=8, eva_chunk=4,
             n_pred_heads=8, norm_unit_offset=True,
             residual_dtype=jnp.float32, compute_dtype=jnp.bfloat16),
        {**_ATTN_MHA, "attn/adaptive_phi": (4, 8),
         "attn/adaptive_mu_k": (4, 8), **_NORMS, **_SWIGLU},
        {"embed": (64, 32), "norm_f/scale": (32,),
         "lm_head/kernel": (32, 8 * 64)},
        None,  # no decode path
    ),
}


def _leaf_shapes(tree):
    return {k: tuple(v.shape)
            for k, v in flatten_dict(dict(tree), sep="/").items()}


@pytest.mark.parametrize("case", list(_TREES))
def test_parameter_and_cache_paths_are_pinned(case):
    """The parameter tree and the cache collection by path and shape, as a
    literal: a rename under a refactor fails here and not in a checkpoint."""
    from tpunet.models import init_cache

    kw, block, top, cache = _TREES[case]
    model = Transformer(**{**_TOY, **kw})
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((2, 16), jnp.int32))["params"]
    want = dict(top)
    for i in range(2):
        want.update({f"block{i}/{k}": v for k, v in block.items()})
    assert _leaf_shapes(params) == want
    if cache is not None:
        got = jax.eval_shape(lambda: init_cache(model, 2, 16))
        assert _leaf_shapes(got) == {
            f"block{i}/{k}": v for i in range(2) for k, v in cache.items()}


def test_moe_top_k_equals_experts_is_dense_mixture():
    """Closed form: with top_k == n_experts and ample capacity nothing is
    dropped and the renormalized gates ARE the softmax probs, so the MoE
    output must equal the dense probs-weighted mixture of every expert."""
    from tpunet.models.transformer import MoeMlp

    e, d, f = 3, 8, 16
    m = MoeMlp(n_experts=e, d_ff=f, capacity_factor=float(e),
               compute_dtype=jnp.float32, top_k=e)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 5, d), jnp.float32)
    variables = m.init(jax.random.PRNGKey(1), x)
    y = m.apply(variables, x)

    p = variables["params"]
    xt = x.reshape(-1, d)
    probs = jax.nn.softmax(xt @ p["router"], axis=-1)  # (t, e)
    dense = jnp.zeros_like(xt)
    for j in range(e):
        hj = jax.nn.gelu(xt @ p["wi"][j])
        dense = dense + probs[:, j:j + 1] * (hj @ p["wo"][j])
    np.testing.assert_allclose(
        np.asarray(y.reshape(-1, d)), np.asarray(dense), atol=1e-5, rtol=1e-5)



def test_moe_top_k_validation():
    from tpunet.models.transformer import MoeMlp

    x = jnp.zeros((1, 4, 8), jnp.float32)
    with pytest.raises(ValueError, match="top_k"):
        MoeMlp(n_experts=4, d_ff=8, top_k=5).init(jax.random.PRNGKey(0), x)
    with pytest.raises(ValueError, match="top_k"):
        MoeMlp(n_experts=4, d_ff=8, top_k=0).init(jax.random.PRNGKey(0), x)
