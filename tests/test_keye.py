"""The Keye-VL-2.0 language model's block through `Transformer`, against the
benchmark's plain reference (perfbench/references/keye.py) on seeded weights
at a toy size: attention whose keys an indexer selects (3 indexer heads of 8
over one key head, the 6 best keys a query), a norm a head on q and k, the
indexer's own loss with gradients disjoint from the cross-entropy's, 8 SwiGLU
experts 3 a token with the router on the expert layer's own input, the
held-range contract, and the train step's loss."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from perfbench import optimizers, weights
from perfbench.models import keye as models
from perfbench.references import keye as ref
from perfbench.references import smallthinker as reglu_ref
from tpunet.models import Transformer
from tpunet.models.transformer import GroupedExperts
from tpunet.train import TrainState, make_train_step
from tpunet.train.trainer import _sown

CFG = {
    "hidden_size": 48, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "moe_intermediate_size": 24, "num_experts": 8,
    "num_experts_per_tok": 3, "num_hidden_layers": 2, "decoder_sparse_step": 1,
    "hidden_act": "silu", "rope_theta": 1e7, "rms_norm_eps": 1e-6,
    "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 3,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 8,
                  "q_chunk_size": 8, "topk": 6},
    "vocab_size": 64, "initializer_range": 0.3, "index_loss_weight": 1.0,
    "compute_dtype": "float32",
}
SEQ = 32
OPT = {"name": "adamw", "learning_rate": 3e-4, "b1": 0.9, "b2": 0.999,
       "eps": 1e-8, "weight_decay": 1e-4}


def _model(cfg=CFG, **kw):
    """The benchmark's own build of the configuration, on the plain forms
    unless a test asks for the kernels."""
    return models.build(cfg, {}).clone(**{"attn_impl": "reference", **kw})


def _params(cfg=CFG, seed=3):
    return weights.generate(ref.param_spec(cfg), seed, jnp.float32)


def _batch(cfg=CFG, rows=2, seed=5):
    toks = jax.random.randint(jax.random.PRNGKey(seed), (rows, SEQ), 0,
                              cfg["vocab_size"], jnp.int32)
    return toks, jnp.roll(toks, -1, axis=1)


def _program_losses(model, params, batch):
    """(mean cross-entropy, mean over the layers of the indexer's loss)."""
    logits, mut = model.apply({"params": params}, batch[0], mutable=["intermediates"])
    index = _sown(mut, "dsa_index_loss")
    return (jnp.mean(optax.softmax_cross_entropy_with_integer_labels(logits, batch[1])),
            sum(index) / len(index))


def _program_loss(model, params, batch, weight=1.0):
    xent, index = _program_losses(model, params, batch)
    return xent + weight * index


def _reference_loss(params, batch, cfg=CFG):
    return ref.loss_rows(params, batch, cfg) / ref.units(batch)


def _close(got, want, tol=2e-4):
    scale = float(jnp.max(jnp.abs(want))) + 1e-6
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=tol * scale)


def _is_indexer(path: str) -> bool:
    return "/attn/index_" in path


def test_the_programs_tree_is_the_references_spec():
    shapes = jax.eval_shape(_model().init, jax.random.PRNGKey(0),
                            jnp.zeros((1, SEQ), jnp.int32))["params"]
    prog = {p: tuple(s.shape) for p, s in weights.flatten(
        jax.tree.map(lambda x: x, dict(shapes))).items()}
    assert prog == {p: tuple(s) for p, (s, _) in ref.param_spec(CFG).items()}
    assert prog["block0/attn/index_q/kernel"] == (48, 3 * 8)
    assert prog["block0/attn/index_k/kernel"] == (48, 8)  # ONE key head
    assert prog["block1/attn/q_norm/scale"] == (16,)


# Tolerances: both sides are float32 on the CPU; the program sums in another
# order (kernels a tile at a time, grouped products a row buffer at a time),
# which moves a sum of some hundred terms by a few 1e-6 of its size. A
# selection that differed in ONE pair would move the loss by 1e-3 and a
# gradient by percents, a lower precision (bfloat16's 4e-3 a product) by more
# than either bound.
@pytest.mark.parametrize("attn_impl", ["reference", "flash"])
def test_logits_loss_and_gradients_match_the_reference(attn_impl):
    model, params, batch = _model(attn_impl=attn_impl), _params(), _batch()
    logits, mut = model.apply({"params": params}, batch[0], mutable=["intermediates"])
    want = [ref.forward_one(params, t, CFG) for t in batch[0]]
    _close(logits, jnp.stack([w[0] for w in want]))
    pairs = sum(w[2] for w in want)
    assert [int(p) for p in _sown(mut, "dsa_selected_pairs")] == [int(p) for p in pairs]
    assert int(pairs[0]) == 2 * sum(min(t + 1, 6) for t in range(SEQ))
    loss, grads = jax.value_and_grad(lambda p: _program_loss(model, p, batch))(params)
    rloss, rgrads = jax.value_and_grad(lambda p: _reference_loss(p, batch))(params)
    assert abs(float(loss) - float(rloss)) < 1e-5 * abs(float(rloss))
    got, want = weights.flatten(grads), weights.flatten(rgrads)
    for path in want:
        _close(got[path], want[path], 1e-3)
    for path in ("block1/moe/router", "block1/attn/index_w/kernel",
                 "block0/attn/index_k_norm/bias", "block0/attn/q_norm/scale"):
        assert float(jnp.linalg.norm(got[path])) > 0, path


def test_one_adamw_step_matches_the_references_update():
    """The train step (its loss and its update, through `make_train_step`)
    against the reference's gradient through the benchmark's own AdamW a
    leaf. 1e-3 of a leaf's largest change: AdamW's first step divides the
    gradient by its own magnitude, so a gradient off by 1e-6 of the leaf's
    largest moves a near-zero entry's step by that much over its size."""
    model, params, batch = _model(), _params(), _batch()
    tx = optimizers.find(OPT).program(OPT)
    state = TrainState(params, tx.init(params), jnp.zeros((), jnp.int32))
    new, loss = make_train_step(model, tx, donate=False)(state, *batch, jax.random.PRNGKey(1))
    rloss, rgrads = jax.value_and_grad(lambda p: _reference_loss(p, batch))(params)
    assert abs(float(loss) - float(rloss)) < 1e-5 * abs(float(rloss))
    got, old = weights.flatten(new.params), weights.flatten(params)
    mod = optimizers.find(OPT)
    for path, g in weights.flatten(rgrads).items():
        slots = tuple(jnp.zeros_like(g) for _ in range(mod.SLOTS))
        want, _ = mod.reference_leaf(jnp.copy(old[path]), slots, g, 1, OPT)  # it donates
        big = np.abs(np.asarray(g)) > 1e-3 * float(jnp.max(jnp.abs(g)))
        np.testing.assert_allclose(np.asarray(got[path] - old[path])[big],
                                   np.asarray(want - old[path])[big],
                                   atol=1e-3 * OPT["learning_rate"])


def test_the_two_losses_gradients_are_disjoint():
    """The cross-entropy reaches no weight of the indexer; the indexer's loss
    reaches nothing else (its input, the target and the selection are
    constants of it)."""
    model, params, batch = _model(attn_impl="flash"), _params(), _batch()
    of = lambda i: weights.flatten(jax.grad(  # noqa: E731
        lambda p: _program_losses(model, p, batch)[i])(params))
    xent, index = of(0), of(1)
    for path in xent:
        theirs, mine = (index, xent) if _is_indexer(path) else (xent, index)
        assert float(jnp.max(jnp.abs(mine[path]))) == 0.0, path
        assert float(jnp.linalg.norm(theirs[path])) > 0.0, path


def test_the_train_steps_loss_adds_the_indexers_at_the_models_weight():
    batch, params = _batch(), _params()
    tx = optax.sgd(0.0)
    state = TrainState(params, tx.init(params), jnp.zeros((), jnp.int32))
    xent, index = (float(x) for x in _program_losses(_model(), params, batch))
    assert index > 1e-3 * xent
    for weight in (1.0, 0.25):
        model = _model(index_loss_weight=weight)
        loss = float(make_train_step(model, tx, donate=False)(
            state, *batch, jax.random.PRNGKey(1))[1])
        assert abs(loss - (xent + weight * index)) < 1e-6 * loss


def test_remat_and_bfloat16_run_the_same_block():
    model, params, batch = _model(attn_impl="flash"), _params(), _batch()
    want = jax.grad(lambda p: _program_loss(model, p, batch))(params)
    got = jax.grad(lambda p: _program_loss(model.clone(remat=True), p, batch))(params)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        _close(a, b, 1e-5)
    half = model.clone(compute_dtype=jnp.bfloat16)
    assert abs(float(_program_loss(half, params, batch))
               - float(_program_loss(model, params, batch))) < 0.05


def _expert_layer(held, u, moe_params, **kw):
    first, count = held
    mine = dict(moe_params, **{n: moe_params[n][first:first + count]
                               for n in ("gate", "up", "down")})
    layer = GroupedExperts(8, 3, 24, held, jnp.float32, **kw)
    return layer.apply({"params": mine}, u, u, mutable=["intermediates"])


def test_eight_shares_add_up_to_the_whole_layer_and_to_the_uncut_reference():
    """The cut the benchmark's configuration makes, an eighth a chip: the 8
    experts held as 8 shares of 1. No share computes what another does
    (there is no shared expert), so the shares' outputs simply add up."""
    u = jax.random.normal(jax.random.PRNGKey(11), (1, 40, 48))
    moe = _params()["block0"]["moe"]
    whole, _ = _expert_layer((0, 8), u, moe, activation="silu")
    shares = [_expert_layer((i, 1), u, moe, activation="silu") for i in range(8)]
    _close(sum(out for out, _ in shares), whole, 1e-5)
    counted = [int(m["intermediates"]["moe_rows_held"][0]) for _, m in shares]
    assert sum(counted) == 40 * 3  # every (token, choice) pair falls on ONE share
    experts, gates = ref.route(u[0], moe["router"], CFG, "f32")
    _close(whole[0], ref.experts_held(u[0], experts, gates, moe, CFG, "f32"), 1e-5)
    cut = dict(CFG, experts_first=5, num_local_experts=1)
    mine = {n: moe[n][5:6] for n in ("gate", "up", "down")}
    _close(shares[5][0][0], ref.experts_held(u[0], experts, gates, mine, cut, "f32"), 1e-5)


def test_silu_and_the_routers_input_against_the_defaults():
    """The two fields this family sets: the gate's function ("relu", PR 30's
    ReGLU, stays the default) and what the router reads (the attention's
    input stays the default)."""
    u = jax.random.normal(jax.random.PRNGKey(11), (1, 40, 48))
    moe = _params()["block0"]["moe"]
    reglu, _ = _expert_layer((0, 8), u, moe)
    silu, _ = _expert_layer((0, 8), u, moe, activation="silu")
    cut = {"moe_num_primary_experts": 8, "moe_num_active_primary_experts": 3}
    experts, gates = reglu_ref.route(u[0], moe["router"], cut, "f32")
    _close(reglu[0], reglu_ref.experts_held(u[0], experts, gates, moe, cut, "f32"), 1e-5)
    assert float(jnp.max(jnp.abs(reglu - silu))) > 1e-2 * float(jnp.max(jnp.abs(silu)))
    with pytest.raises(ValueError, match="unknown moe_activation"):
        _expert_layer((0, 8), u, moe, activation="gelu")
    # the router's input: a model that differs in that field alone routes,
    # and so answers, differently, and only "mlp_input" is the reference's
    params, batch = _params(), _batch()
    logits = _model().apply({"params": params}, batch[0])
    early = _model(moe_router_input="attn_input").apply({"params": params}, batch[0])
    assert float(jnp.max(jnp.abs(logits - early))) > 1e-3 * float(jnp.max(jnp.abs(logits)))
    with pytest.raises(ValueError, match="unknown moe_router_input"):
        _model(moe_router_input="residual").apply({"params": params}, batch[0])
    plain = Transformer(d_model=96, n_heads=4).layer_specs()
    assert {(sp.moe_activation, sp.moe_router_input, sp.qk_norm, sp.attn_select_top_k,
             sp.attn_index_heads) for sp in plain} == {("relu", "attn_input", False, None, 0)}


def test_decode_with_a_selection_is_refused_and_says_why():
    model = _model().clone(decode=True)
    with pytest.raises(ValueError, match="cache of the indexer's keys"):
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    with pytest.raises(ValueError, match="attn_select_top_k needs"):
        _model(attn_window=8).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    with pytest.raises(ValueError, match="attn_select_top_k needs"):
        _model(attn_index_heads=0).init(jax.random.PRNGKey(0),
                                        jnp.zeros((1, 8), jnp.int32))


# -- what this family's fields left alone --------------------------------------------

# The lowered train step (StableHLO text of `make_train_step(...).lower`, CPU
# backend, kernels through the interpreter) of three toy models that use none
# of the fields PR 33 added, hashed at the PARENT commit 0c87096. A change to
# `Block`, `SelfAttention`, `GroupedExperts` or `_make_loss_fn` that alters
# what those models compute alters the text. A PR that means to change them
# prints the new hashes with `_lowered_hash` and says so.
UNTOUCHED = {
    "flash_window": (
        dict(vocab=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2, d_ff=64,
             mlp_impl="swiglu", attn_impl="flash", attn_window=8, remat=True),
        "5b3e42c0b1a5ebb9f2597373bffe598fb7b90b681e320534619296a548eba41f"),
    "eva": (
        dict(vocab=64, d_model=32, n_layers=2, n_heads=2, d_ff=64, mlp_impl="swiglu",
             attn_impl="eva", eva_window=16, eva_chunk=4, n_pred_heads=2,
             residual_dtype=jnp.float32, norm_unit_offset=True, remat=True),
        "80352084578bdb497a2cabbd7dad3c968f4ca2e8b24dee46dfae40af0b0dc439"),
    "reglu_grouped": (
        dict(vocab=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2, head_dim=16,
             d_ff=16, n_experts=8, moe_every=1, moe_top_k=3, moe_impl="grouped",
             moe_held=(0, 4), attn_impl="flash", attn_window=8,
             attn_pattern=((False, False), (True, True)), remat=True),
        "1e441ff7292143d3fd9d1022e335b689d74e8f5493ce958f669dec75065330c6"),
}


def _lowered_hash(fields: dict) -> str:
    model = Transformer(**fields)
    toks = jnp.zeros((2, 32), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), toks)["params"]
    tx = optax.adamw(3e-4)
    state = jax.eval_shape(
        lambda p: TrainState(p, tx.init(p), jnp.zeros((), jnp.int32)), params)
    text = make_train_step(model, tx).lower(
        state, toks, toks, jax.random.PRNGKey(1)).as_text()
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize("name", sorted(UNTOUCHED))
def test_the_lowered_step_of_a_model_without_the_new_fields_is_the_parents(name):
    fields, parents = UNTOUCHED[name]
    assert _lowered_hash(fields) == parents
