"""Nemotron-H through `Transformer`, against the benchmark's plain reference
(perfbench/references/nemotron_h.py) on seeded weights at a toy size: the
chunked scan's kernels (tpunet/ops/ssd_scan.py, in Pallas' interpreter)
against the token-by-token recurrence, blocks of one sublayer by the
pattern, the Mamba-2 mixer, the latent relu2 experts with a sigmoid router
and a shared expert, the multi-token-prediction module and its loss, the
shares a chip holds, and what the new fields leave alone."""

import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from perfbench import harness, optimizers, weights
from perfbench.models import nemotron_h as models
from perfbench.references import nemotron_h as ref
from tpunet.models import Transformer
from tpunet.models.transformer import GroupedExperts, Mamba2, SelfAttention
from tpunet.train import TrainState, make_train_step
from tpunet.train.trainer import _make_loss_fn, _sown

CFG = dict(harness.load("configs", "nemotron3-super-120b-a12b-tp8-l11"),
           hidden_size=32, mamba_num_heads=4, mamba_head_dim=8, n_groups=2,
           ssm_state_size=16, chunk_size=16, num_attention_heads=4,
           num_key_value_heads=2, head_dim=8, n_routed_experts=16,
           n_routed_experts_held=4, n_routed_experts_first=4, num_experts_per_tok=5,
           moe_latent_size=16, moe_intermediate_size=24,
           moe_shared_expert_columns_held=12, vocab_size=64, num_hidden_layers=5,
           hybrid_override_pattern="MEM*E", compute_dtype="float32",
           initializer_range=0.2)
SEQ = 40  # two and a half chunks of 16
OPT = {"name": "adamw", "learning_rate": 3e-4, "b1": 0.9, "b2": 0.999,
       "eps": 1e-8, "weight_decay": 1e-4}


def _model(cfg=CFG, **kw):
    return models.build(cfg, {}).clone(**kw)


def _params(cfg=CFG, seed=3):
    return weights.generate(ref.param_spec(cfg), seed, jnp.float32)


def _batch(cfg=CFG, rows=2, seed=5):
    toks = jax.random.randint(jax.random.PRNGKey(seed), (rows, SEQ), 0,
                              cfg["vocab_size"], jnp.int32)
    return toks, jnp.roll(toks, -1, axis=1)


def _program_losses(model, params, batch):
    """(mean cross-entropy, the MTP module's loss)."""
    logits, mut = model.apply({"params": params}, batch[0], mutable=["intermediates"])
    (mtp,) = _sown(mut, "mtp_loss")
    return (jnp.mean(optax.softmax_cross_entropy_with_integer_labels(logits, batch[1])),
            mtp)


_losses = jax.jit(_program_losses, static_argnums=0)


def _reference_loss(params, batch, cfg=CFG):
    return ref.loss_rows(params, batch, cfg) / ref.units(batch)


@functools.lru_cache(maxsize=None)
def _reference_value_and_grad():
    """The reference's loss and gradient on the toy's weights and batch,
    made once for the tests that compare with them."""
    batch = _batch()
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(lambda p: _reference_loss(p, batch)))(_params())


def _close(got, want, tol=2e-4):
    scale = float(jnp.max(jnp.abs(want))) + 1e-6
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=tol * scale)


# -- the model against the reference ------------------------------------------------

def test_the_programs_tree_is_the_references_spec():
    shapes = jax.eval_shape(_model().init, jax.random.PRNGKey(0),
                            jnp.zeros((1, SEQ), jnp.int32))["params"]
    prog = {p: tuple(s.shape) for p, s in weights.flatten(dict(shapes)).items()}
    assert prog == {p: tuple(s) for p, (s, _) in ref.param_spec(CFG).items()}
    assert prog["block0/mamba/in_proj/kernel"] == (32, 2 * 32 + 2 * 2 * 16 + 4)
    assert prog["block1/moe/up"] == (4, 16, 24)  # relu2: no gate
    assert prog["mtp_block1/moe/shared_down"] == (12, 32)
    assert prog["mtp_proj/kernel"] == (64, 32)


def test_blocks_are_one_sublayer_by_the_pattern():
    specs = _model().layer_specs()
    assert [sp.kind for sp in specs] == list("MEM*E")
    assert [sp.kind for sp in _model().mtp_specs()] == list("*E")
    assert [sp.n_experts for sp in specs] == [0, 16, 0, 0, 16]
    assert {(sp.rotary, sp.attn_window) for sp in specs} == {(False, None)}
    assert {sp.kind for sp in Transformer(d_model=32, n_heads=4).layer_specs()} == {None}
    with pytest.raises(ValueError, match="names 4 blocks"):
        _model(layer_pattern="MEM*").init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    with pytest.raises(ValueError, match="unknown layer kinds"):
        _model(layer_pattern="MEM-E").layer_specs()


# Tolerances: both sides float32 on the CPU, the program summing in another
# order (a chunk at a time, grouped products a row buffer at a time); a
# bfloat16 product (4e-3) or a lost chunk state moves them by far more.
def test_logits_both_losses_and_gradients_match_the_reference():
    model, params, batch = _model(), _params(), _batch()
    with jax.default_matmul_precision("highest"):
        logits = jax.jit(model.apply)({"params": params}, batch[0])
        want = jax.jit(jax.vmap(lambda t: ref.forward_one(params, t, CFG)))(batch[0])
        _close(logits, want[0])
        xent, mtp = _losses(model, params, batch)
        inside = jnp.arange(SEQ) < SEQ - 2
        nll = jax.vmap(ref._nll)(want[1], jnp.roll(batch[0], -2, axis=1))
        mtp_ref = float(jnp.sum(jnp.where(inside, nll, 0.0))) / (2 * (SEQ - 2))
        assert abs(float(mtp) - mtp_ref) < 1e-5 * mtp_ref
        loss, grads = jax.jit(jax.value_and_grad(lambda p: (lambda a, b: a + 0.3 * b)(
            *_program_losses(model, p, batch))))(params)
    rloss, rgrads = _reference_value_and_grad()
    assert abs(float(loss) - float(rloss)) < 1e-5 * abs(float(rloss))
    got, want = weights.flatten(grads), weights.flatten(rgrads)
    for path in want:
        _close(got[path], want[path], 1e-3)
    for path in ("block0/mamba/A_log", "block0/mamba/conv_bias", "block2/mamba/dt_bias",
                 "block1/moe/router", "block1/moe/to_latent", "mtp_proj/kernel",
                 "mtp_block1/moe/shared_up", "mtp_norm_e/scale"):
        assert float(jnp.linalg.norm(got[path])) > 0, path
    assert float(jnp.max(jnp.abs(got["block1/moe/router_bias"]))) == 0.0


def test_one_adamw_step_matches_the_references_update():
    """The train step (its loss, MTP's at the configuration's weight, and
    its update) against the reference's gradient through the benchmark's
    own AdamW a leaf."""
    model, params, batch = _model(), _params(), _batch()
    tx = optimizers.find(OPT).program(OPT)
    state = TrainState(params, tx.init(params), jnp.zeros((), jnp.int32))
    with jax.default_matmul_precision("highest"):
        new, loss = make_train_step(model, tx, donate=False)(state, *batch,
                                                              jax.random.PRNGKey(1))
    rloss, rgrads = _reference_value_and_grad()
    assert abs(float(loss) - float(rloss)) < 1e-5 * abs(float(rloss))
    got, old = weights.flatten(new.params), weights.flatten(params)
    mod = optimizers.find(OPT)
    for path, g in weights.flatten(rgrads).items():
        slots = tuple(jnp.zeros_like(g) for _ in range(mod.SLOTS))
        want, _ = mod.reference_leaf(jnp.copy(old[path]), slots, g, 1, OPT)
        big = np.abs(np.asarray(g)) > 1e-3 * float(jnp.max(jnp.abs(g)) + 1e-30)
        np.testing.assert_allclose(np.asarray(got[path] - old[path])[big],
                                   np.asarray(want - old[path])[big],
                                   atol=1e-3 * OPT["learning_rate"])


@pytest.mark.parametrize("weight", [0.3, 1.0])
def test_the_train_steps_loss_adds_mtp_at_the_models_weight(weight):
    batch, params = _batch(), _params()
    xent, mtp = (float(x) for x in _losses(
        _model(), params, batch))
    objective = _make_loss_fn(_model(mtp_loss_weight=weight), *batch, jax.random.PRNGKey(1), 0.01)
    loss = float(jax.jit(objective)(params))
    assert abs(loss - (xent + weight * mtp)) < 1e-5 * loss


def test_remat_runs_the_same_blocks():
    cfg = dict(CFG, num_hidden_layers=2, hybrid_override_pattern="ME")
    model, params, batch = _model(cfg), _params(cfg), _batch(cfg)
    f = lambda m: jax.jit(jax.grad(lambda p: sum(_program_losses(m, p, batch))))(params)  # noqa: E731
    for a, b in zip(jax.tree.leaves(f(model.clone(remat=True))), jax.tree.leaves(f(model))):
        _close(a, b, 1e-5)


# -- the shares a chip holds --------------------------------------------------------

def _spec(**kw):
    fields = dict(d_model=32, n_heads=16, n_kv_heads=2, head_dim=4, mamba_heads=8,
                  mamba_head_dim=4, mamba_groups=4, mamba_state=8, mamba_chunk=16,
                  attn_pattern=((False, False),), attn_impl="reference",
                  compute_dtype=jnp.float32, n_layers=1, layer_pattern="M")
    fields.update(kw)
    return Transformer(**fields).layer_specs()[0]


def test_the_group_shares_of_a_mamba_layer_add_up_to_the_uncut_layer():
    """Eight heads over four groups, cut as the deployment cuts 128 over 8:
    share g holds group g's B and C, its heads' columns of z, x and dt, their
    conv channels and dt_bias, A_log, D, its group's norm and its rows of
    W_out; the shares' outputs (each a partial of W_out) add up."""
    whole = _spec()
    x = jax.random.normal(jax.random.PRNGKey(1), (2, SEQ, 32))
    params = jax.eval_shape(Mamba2(whole).init, jax.random.PRNGKey(2), x)["params"]
    params = jax.tree.map(lambda p: 0.3 * jax.random.normal(
        jax.random.PRNGKey(p.size), p.shape), params)
    want = jax.jit(Mamba2(whole).apply)({"params": params}, x)
    heads, p, g, n = 8, 4, 4, 8
    inner = heads * p
    per = heads // g
    share = jax.jit(Mamba2(_spec(mamba_heads=per, mamba_groups=1)).apply)
    total = 0
    for k in range(g):
        ch = np.arange(k * per * p, (k + 1) * per * p)     # its heads' channels
        hd = np.arange(k * per, (k + 1) * per)              # its heads
        bc = np.arange(k * n, (k + 1) * n)                  # its group's B (or C)
        cols = np.concatenate([ch, inner + ch, 2 * inner + bc, 2 * inner + g * n + bc,
                               2 * inner + 2 * g * n + hd])
        conv = np.concatenate([ch, inner + bc, inner + g * n + bc])
        mine = {"in_proj": {"kernel": params["in_proj"]["kernel"][:, cols]},
                "conv_kernel": params["conv_kernel"][:, conv],
                "conv_bias": params["conv_bias"][conv],
                **{name: params[name][hd] for name in ("dt_bias", "A_log", "D")},
                "norm_scale": params["norm_scale"][ch],
                "out_proj": {"kernel": params["out_proj"]["kernel"][ch]}}
        total = total + share({"params": mine}, x)
    _close(total, want, 1e-5)


def test_the_head_shares_of_the_attention_add_up_to_the_uncut_attention():
    """16 query heads over 2 KV heads in 8 shares of 2 query heads and the
    one KV head they read, as 32 over 2 in shares of 4."""
    whole = _spec(layer_pattern="*")
    x = jax.random.normal(jax.random.PRNGKey(1), (2, SEQ, 32))
    params = SelfAttention(whole).init(jax.random.PRNGKey(2), x)["params"]
    want = SelfAttention(whole).apply({"params": params}, x)
    total = 0
    for k in range(8):
        q = np.arange(k * 2 * 4, (k + 1) * 2 * 4)
        kv = np.arange((k * 2 // 8) * 4, (k * 2 // 8 + 1) * 4)
        mine = {"q": {"kernel": params["q"]["kernel"][:, q]},
                "k": {"kernel": params["k"]["kernel"][:, kv]},
                "v": {"kernel": params["v"]["kernel"][:, kv]},
                "out": {"kernel": params["out"]["kernel"][q]}}
        total = total + SelfAttention(_spec(layer_pattern="*", n_heads=2, n_kv_heads=1)).apply(
            {"params": mine}, x)
    _close(total, want, 1e-5)


def _mine(params, first, count, shared):
    """The share's leaves: experts first .. first + count - 1, and the
    shared expert's columns `shared` (None: no shared expert)."""
    mine = dict(params, up=params["up"][first:first + count],
                down=params["down"][first:first + count])
    if shared is None:
        return {k: v for k, v in mine.items() if not k.startswith("shared_")}
    return dict(mine, shared_up=params["shared_up"][:, shared],
                shared_down=params["shared_down"][shared])


@functools.lru_cache(maxsize=None)
def _layer(held, shared_cols):
    layer = GroupedExperts(64, 6, 12, held, jnp.float32, "relu2", "sigmoid", 5.0, 8,
                           shared_cols)
    return jax.jit(lambda p, u: layer.apply({"params": p}, u, u, mutable=["intermediates"]))


def _latent(held, shared, u, params):
    return _layer(held, 0 if shared is None else len(shared))(
        _mine(params, *held, shared), u)


def _latent_rolled(k, shared, u, params):
    """Expert k's share run as expert 0 of the experts rolled by k (the
    router's columns and bias with them): the choice of a token does not
    depend on the experts' order, so it is the share of (k, 1), and every
    share is one compiled program."""
    rolled = dict(params, router=jnp.roll(params["router"], -k, axis=1),
                  router_bias=jnp.roll(params["router_bias"], -k),
                  up=jnp.roll(params["up"], -k, axis=0), down=jnp.roll(params["down"], -k, axis=0))
    return _latent((0, 1), shared, u, rolled)


def test_the_expert_ranges_and_shared_slices_add_up_to_the_uncut_layer():
    """64 experts held one a share (512 as 8 a chip over 64 chips) and the
    shared expert's 16 columns in 8 slices of 2 (5,376 as 672 a chip over 8):
    the router and the latent projections are every share's, counted once;
    the shares' outputs add up to the whole layer and to the reference's."""
    u = jax.random.normal(jax.random.PRNGKey(11), (1, SEQ, 16))
    cfg = dict(CFG, hidden_size=16, n_routed_experts=64, n_routed_experts_held=64,
               n_routed_experts_first=0, num_experts_per_tok=6, moe_latent_size=8,
               moe_intermediate_size=12, moe_shared_expert_columns_held=16,
               initializer_range=0.3)
    spec = ref._moe_spec(cfg, "moe")
    moe = weights.generate(spec, 4, jnp.float32)["moe"]
    moe["router_bias"] = 0.1 * jax.random.normal(jax.random.PRNGKey(3), (64,))
    whole, _ = _latent((0, 64), np.arange(16), u, moe)
    slices = [np.arange(2 * k, 2 * k + 2) if k < 8 else None for k in range(64)]
    shares = [_latent_rolled(k, slices[k], u, moe) for k in range(64)]
    for k in (5, 63):  # a held range that does not start at 0, as the program holds it
        _close(_latent((k, 1), slices[k], u, moe)[0], shares[k][0], 1e-6)
    _close(sum(out for out, _ in shares), whole, 1e-5)
    counted = [int(m["intermediates"]["moe_rows_held"][0]) for _, m in shares]
    assert sum(counted) == SEQ * 6
    with jax.default_matmul_precision("highest"):
        _close(whole[0], ref.latent_moe(u[0], moe, cfg, "f32"), 1e-5)


def test_the_latent_fields_default_to_the_parents_experts():
    assert GroupedExperts(8, 2, 4).scoring == "softmax"
    spec = Transformer(d_model=32, n_heads=4).layer_specs()[0]
    assert (spec.moe_scoring, spec.moe_routed_scale, spec.moe_latent, spec.moe_shared_d_ff,
            spec.mamba_heads) == ("softmax", 1.0, 0, 0, 0)
    with pytest.raises(ValueError, match="unknown moe_scoring"):
        GroupedExperts(8, 2, 4, scoring="tanh").init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4, 8)), jnp.zeros((1, 4, 8)))


# -- refusals ------------------------------------------------------------------------

def test_decode_with_a_mamba_layer_or_mtp_is_refused_and_says_why():
    toks = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(ValueError, match="recurrent state"):
        _model(mtp_pattern=None, decode=True).init(jax.random.PRNGKey(0), toks)
    with pytest.raises(ValueError, match="draft source"):
        _model(decode=True).init(jax.random.PRNGKey(0), toks)
    with pytest.raises(ValueError, match="features_only"):
        _model().init(jax.random.PRNGKey(0), toks, features_only=True)


# -- what this family's fields left alone --------------------------------------------

# The lowered train step (StableHLO text of `make_train_step(...).lower`, CPU
# backend, kernels through the interpreter) of toy models of the families
# that came before, none using a field this family added, hashed at the
# PARENT commit fdf7ba6: flash with a window, EVA, ReGLU grouped experts
# (tests/test_keye.py's three), SwiGLU grouped experts under the selecting
# attention, and the plain dense decoder.
UNTOUCHED = {
    "swiglu_dsa": (
        dict(vocab=64, d_model=48, n_layers=2, n_heads=4, n_kv_heads=2, head_dim=16,
             d_ff=24, n_experts=8, moe_every=1, moe_top_k=3, moe_impl="grouped",
             moe_held=(2, 2), moe_activation="silu", moe_router_input="mlp_input",
             qk_norm=True, attn_select_top_k=6, attn_index_heads=3,
             attn_index_head_dim=8, attn_impl="flash", rope_theta=1e7,
             compute_dtype=jnp.float32, remat=True),
        "82ccbbcca029da83fa97ed66a727ccf3da2c90411d4870e3cfa62095004c1e69"),
    "dense": (dict(vocab=64, d_model=32, n_layers=2, n_heads=4, d_ff=64),
              "243585445f75ee82777e4c0b270ca0ad565bb3c4d86322e0903e9308c84233ae"),
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
def test_the_lowered_step_of_an_earlier_family_is_the_parents(name):
    fields, parents = UNTOUCHED[name]
    assert _lowered_hash(fields) == parents


def test_the_configuration_is_the_catalog_row_but_for_its_cut():
    """Every number of the row's config stands in the configuration's file
    under its key, changed only where `reduced` names it; the widths are the
    published ones."""
    cfg = harness.load("configs", "nemotron3-super-120b-a12b-tp8-l11")
    row = {"num_hidden_layers": 88, "vocab_size": 131072, "mamba_num_heads": 128,
           "n_groups": 8, "num_attention_heads": 32, "num_key_value_heads": 2}
    for key, value in row.items():
        assert cfg["published"][key] == value and cfg[key] != value, key
    assert set(cfg["published"]) == set(cfg["reduced"])
    entry = next(c for c in harness.manifest()["configs"]
                 if c["name"] == "nemotron3-super-120b-a12b-tp8-l11")
    assert entry["reduced"] == cfg["reduced"]
    for key in ("hidden_size", "mamba_head_dim", "ssm_state_size", "chunk_size",
                "conv_kernel", "head_dim", "moe_latent_size", "moe_intermediate_size",
                "moe_shared_expert_intermediate_size", "num_experts_per_tok",
                "n_routed_experts"):
        assert key not in cfg["reduced"], key
    assert models.pattern(cfg) == "MEMEMEM*EME" and models.mtp_pattern(cfg) == "*E"
    assert (models.layers_of(cfg, "M"), models.layers_of(cfg, "E"),
            models.layers_of(cfg, "*")) == (5, 6, 2)
