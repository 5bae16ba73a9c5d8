"""The SmallThinker block through `Transformer`, against the benchmark's
plain reference (perfbench/references/smallthinker.py) on seeded weights at
a toy size: a head size free of the width, GQA group 7, one full NoPE layer
among three rotary window layers, 8 gated experts 3 a token with the router
on the attention's input, nothing dropped, and the held-range contract
(routes over all, computes its own, adds nothing for the absent)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from perfbench import weights
from perfbench.models import smallthinker as models
from perfbench.references import smallthinker as ref
from tpunet.models import Transformer
from tpunet.models.transformer import GroupedExperts
from tpunet.ops import grouped_matmul as gm
from tpunet.train import TrainState, make_train_step

CFG = {
    "hidden_size": 48, "num_attention_heads": 7, "num_key_value_heads": 1,
    "head_dim": 16, "moe_ffn_hidden_size": 24, "moe_num_primary_experts": 8,
    "moe_num_active_primary_experts": 3, "num_hidden_layers": 4,
    "rope_layout": [0, 1, 1, 1], "sliding_window_layout": [0, 1, 1, 1],
    "sliding_window_size": 8, "rope_theta": 1.5e6, "rms_norm_eps": 1e-6,
    "vocab_size": 64, "initializer_range": 0.3, "compute_dtype": "float32",
}
SEQ = 32


def _model(cfg=CFG, **kw):
    """The benchmark's own build of the configuration, on the plain
    attention unless a test asks for the kernels."""
    return models.build(cfg, {}).clone(**{"attn_impl": "reference", **kw})


def _params(cfg=CFG, seed=3):
    return weights.generate(ref.param_spec(cfg), seed, jnp.float32)


def _batch(cfg=CFG, rows=2, seed=5):
    toks = jax.random.randint(jax.random.PRNGKey(seed), (rows, SEQ), 0,
                              cfg["vocab_size"], jnp.int32)
    return toks, jnp.roll(toks, -1, axis=1)


def _program_loss(model, params, batch):
    logits = model.apply({"params": params}, batch[0])
    return jnp.mean(optax.softmax_cross_entropy_with_integer_labels(logits, batch[1]))


def _close(got, want, tol=2e-4):
    scale = float(jnp.max(jnp.abs(want))) + 1e-6
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=tol * scale)


def test_the_programs_tree_is_the_references_spec():
    shapes = jax.eval_shape(_model().init, jax.random.PRNGKey(0),
                            jnp.zeros((1, SEQ), jnp.int32))["params"]
    prog = {p: tuple(s.shape) for p, s in weights.flatten(
        jax.tree.map(lambda x: x, dict(shapes))).items()}
    assert prog == {p: tuple(s) for p, (s, _) in ref.param_spec(CFG).items()}
    assert prog["block0/attn/q/kernel"] == (48, 7 * 16)  # not d_model wide
    assert prog["block0/moe/router"] == (48, 8)


@pytest.mark.parametrize("attn_impl", ["reference", "flash"])
def test_logits_loss_and_gradients_match_the_reference(attn_impl):
    model, params, batch = _model(attn_impl=attn_impl), _params(), _batch()
    logits = model.apply({"params": params}, batch[0])
    want = jnp.stack([ref.logits_one(params, t, CFG) for t in batch[0]])
    _close(logits, want)
    loss, grads = jax.value_and_grad(lambda p: _program_loss(model, p, batch))(params)
    rloss, rgrads = jax.value_and_grad(
        lambda p: ref.loss_rows(p, batch, CFG) / ref.units(batch))(params)
    assert abs(float(loss) - float(rloss)) < 1e-5 * abs(float(rloss))
    got, want = weights.flatten(grads), weights.flatten(rgrads)
    for path in want:
        _close(got[path], want[path], 1e-3)
    assert float(jnp.linalg.norm(got["block2/moe/router"])) > 0


def _expert_layer(held, h, u, moe_params):
    first, count = held
    mine = dict(moe_params, **{n: moe_params[n][first:first + count]
                               for n in ("gate", "up", "down")})
    layer = GroupedExperts(8, 3, 24, held, jnp.float32)
    return layer.apply({"params": mine}, u, h, mutable=["intermediates"])


def _layer_inputs(seed=11, tokens=40):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.random.normal(k1, (1, tokens, 48)),
            jax.random.normal(k2, (1, tokens, 48)))


def test_four_shares_add_up_to_the_whole_layer_and_to_the_uncut_reference():
    """The cut the benchmark's configuration makes: the 8 experts held as 4
    shares of 2. No share computes what another does (there is no shared
    expert), so the shares' outputs simply add up."""
    moe = _params()["block1"]["moe"]
    h, u = _layer_inputs()
    whole, mut = _expert_layer((0, 8), h, u, moe)
    shares = [_expert_layer((2 * i, 2), h, u, moe) for i in range(4)]
    _close(sum(out for out, _ in shares), whole, 1e-5)
    counted = [int(m["intermediates"]["moe_rows_held"][0]) for _, m in shares]
    assert sum(counted) == int(mut["intermediates"]["moe_rows_held"][0]) == 40 * 3
    experts, gates = ref.route(h[0], moe["router"], CFG, "f32")
    uncut = ref.experts_held(u[0], experts, gates, moe, CFG, "f32")
    _close(whole[0], uncut, 1e-5)
    cut = dict(CFG, moe_experts_first=2, moe_num_primary_experts_held=2)
    mine = {n: moe[n][2:4] for n in ("gate", "up", "down")}
    _close(shares[1][0][0], ref.experts_held(u[0], experts, gates, mine, cut, "f32"), 1e-5)


def _planted(moe, chosen):
    """A router under which every token of an all-ones h chooses `chosen`."""
    router = np.full((48, 8), -1.0, np.float32)
    for rank, e in enumerate(chosen):
        router[:, e] = 1.0 + 0.1 * rank
    return dict(moe, router=jnp.asarray(router))


def test_nothing_is_dropped_when_every_token_chooses_the_same_experts():
    moe = _planted(_params()["block1"]["moe"], (1, 4, 6))
    _, u = _layer_inputs(tokens=40)
    h = jnp.ones_like(u)
    out, mut = _expert_layer((0, 8), h, u, moe)
    assert int(mut["intermediates"]["moe_rows_max"][0]) == 40      # = tokens
    assert int(mut["intermediates"]["moe_rows_held"][0]) == 40 * 3
    experts, gates = ref.route(h[0], moe["router"], CFG, "f32")
    assert sorted(np.unique(np.asarray(experts))) == [1, 4, 6]
    _close(out[0], ref.experts_held(u[0], experts, gates, moe, CFG, "f32"), 1e-5)
    # the static buffer holds the worst case: three full groups, five empty
    tile_m = gm.tile_rows(40 * 3, jnp.float32)
    rows = gm.buffer_rows(40 * 3, 8, tile_m)
    sizes = jnp.asarray([0, 40, 0, 0, 40, 0, 40, 0], jnp.int32)
    starts, _, n_tiles = gm.group_tiles(sizes, tile_m, rows)
    assert int(n_tiles[0]) * tile_m <= rows
    assert int(starts[6]) + 40 <= rows


def test_a_held_range_that_gets_no_row_gives_zeros_and_finite_gradients():
    moe = _planted(_params()["block1"]["moe"], (0, 1, 2))
    _, u = _layer_inputs()
    h = jnp.ones_like(u)

    def f(moe, u):
        out, mut = _expert_layer((6, 2), h, u, moe)
        return jnp.sum(out ** 2) + jnp.sum(out), (out, mut)

    (_, (out, mut)), grads = jax.value_and_grad(f, (0, 1), has_aux=True)(moe, u)
    assert not np.asarray(out).any()
    assert int(mut["intermediates"]["moe_rows_held"][0]) == 0
    for leaf in jax.tree.leaves(grads):
        assert np.isfinite(np.asarray(leaf)).all()
        assert not np.asarray(leaf).any()


def _choice_histogram(params, tokens):
    """How many (token, choice) pairs each of the 8 experts gets in block 0,
    read through eight one-expert shares' `moe_rows_held`."""
    one = dict(CFG, num_hidden_layers=1)
    out = []
    for e in range(8):
        mine = jax.tree.map(lambda x: x, params)
        mine["block0"]["moe"] = dict(
            params["block0"]["moe"],
            **{n: params["block0"]["moe"][n][e:e + 1] for n in ("gate", "up", "down")})
        _, mut = _model(one, moe_held=(e, 1)).apply(
            {"params": mine}, tokens, mutable=["intermediates"])
        out.append(int(mut["intermediates"]["block0"]["moe"]["moe_rows_held"][0]))
    return out


def test_the_router_reads_the_attentions_input():
    """Attention's output does not reach the router: another `out` kernel
    leaves every choice as it was, another embedding does not."""
    params = {k: v for k, v in _params().items() if k in (
        "embed", "norm_f", "lm_head", "block0")}
    tokens = _batch()[0]
    before = _choice_histogram(params, tokens)
    assert sum(before) == tokens.size * 3
    other = jax.tree.map(lambda x: x, params)
    other["block0"]["attn"]["out"]["kernel"] = 10.0 * jax.random.normal(
        jax.random.PRNGKey(9), params["block0"]["attn"]["out"]["kernel"].shape)
    assert _choice_histogram(other, tokens) == before
    other = dict(params, embed=jax.random.normal(jax.random.PRNGKey(9),
                                                 params["embed"].shape))
    assert _choice_histogram(other, tokens) != before


def test_a_layer_without_the_rotary_matches_the_reference_without_it():
    one = dict(CFG, num_hidden_layers=1)
    params = {k: v for k, v in _params().items() if k in (
        "embed", "norm_f", "lm_head", "block0")}
    tokens = _batch()[0]
    logits = _model(one).apply({"params": params}, tokens)
    bare = jnp.stack([ref.logits_one(params, t, one) for t in tokens])
    roped = jnp.stack([ref.logits_one(params, t, dict(one, rope_layout=[1]))
                       for t in tokens])
    _close(logits, bare)
    assert float(jnp.max(jnp.abs(logits - roped))) > 1e-2 * float(jnp.max(jnp.abs(bare)))
    # and the window layers take the rotary: the other way round differs too
    logits = _model(one, attn_pattern=((False, True),)).apply({"params": params}, tokens)
    _close(logits, roped)


def test_the_train_steps_loss_is_the_cross_entropy_alone():
    """GroupedExperts sows nothing under `moe_aux_loss`, so `moe_aux_weight`
    (0.01 by default, which the benchmark's adapter leaves) adds nothing."""
    model, batch = _model(), _batch()
    tx = optax.sgd(0.0)
    params = _params()
    state = TrainState(params, tx.init(params), jnp.zeros((), jnp.int32))
    key = jax.random.PRNGKey(1)
    losses = [float(make_train_step(model, tx, donate=False, moe_aux_weight=w)(
        state, *batch, key)[1]) for w in (0.01, 10.0)]
    want = float(_program_loss(model, state.params, batch))
    assert losses[0] == losses[1]
    assert abs(losses[0] - want) < 1e-6 * want
    _, mut = model.apply({"params": state.params}, batch[0], mutable=["intermediates"])
    names = {getattr(k, "key", None) for path, _ in
             jax.tree_util.tree_leaves_with_path(mut["intermediates"]) for k in path}
    assert {"moe_rows_held", "moe_rows_max"} <= names and "moe_aux_loss" not in names


def test_remat_and_bfloat16_run_the_same_block():
    model, params, batch = _model(), _params(), _batch()
    want = jax.grad(lambda p: _program_loss(model, p, batch))(params)
    got = jax.grad(lambda p: _program_loss(model.clone(remat=True), p, batch))(params)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        _close(a, b, 1e-5)
    half = model.clone(compute_dtype=jnp.bfloat16)
    assert abs(float(_program_loss(half, params, batch))
               - float(_program_loss(model, params, batch))) < 0.05


def test_layer_specs_place_the_kinds_of_attention():
    specs = _model().clone(n_layers=6).layer_specs()
    assert [(sp.attn_window, sp.rotary) for sp in specs] == [
        (None, False), (8, True), (8, True), (8, True), (None, False), (8, True)]
    assert {(sp.head_dim, sp.moe_impl, sp.moe_held) for sp in specs} == {
        (16, "grouped", (0, 8))}
    plain = Transformer(d_model=96, n_heads=4, attn_window=8).layer_specs()
    assert {(sp.attn_window, sp.rotary, sp.head_dim, sp.moe_impl, sp.moe_held)
            for sp in plain} == {(8, True, 24, "capacity", None)}


def test_decode_with_two_kinds_of_layer_is_refused_and_says_why():
    model = _model().clone(decode=True)
    with pytest.raises(ValueError, match="more than one kind of layer"):
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    with pytest.raises(ValueError, match="unknown moe_impl"):
        _model(moe_impl="sorted").init(jax.random.PRNGKey(0),
                                       jnp.zeros((1, 8), jnp.int32))
    with pytest.raises(ValueError, match="outside n_experts"):
        _model(moe_held=(6, 4)).init(jax.random.PRNGKey(0),
                                     jnp.zeros((1, 8), jnp.int32))
