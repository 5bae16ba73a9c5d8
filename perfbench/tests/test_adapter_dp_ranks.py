"""The dp_ranks adapter end to end at a tiny size on the CPU, two ranks over
loopback, the harness's look for a chip skipped: the result line's keys,
`correct` true on a sound run, `correct` false once for each fault the timed
path can have and for the control; and the shape twin's tree."""

import jax
import numpy as np
import pytest

from perfbench import compare, harness, twin, weights
from perfbench.adapters import _models, _train, dp_ranks
from perfbench.harness import check_line


def test_dp_ranks_two_ranks_over_loopback(dp_cell):
    res = dp_ranks.run(dp_cell, 12345, 2.0, True, platform="cpu")
    check_line(res, traced=True)
    assert res["correct"], res["compared"]
    assert res["metrics"]["dcn_ring_s_per_step"]["value"] > 0
    assert res["metrics"]["transport_syscalls_per_mib"]["value"] > 0


@pytest.mark.parametrize("fault", ["no_exchange", "half_batch", "state_unchanged"])
def test_dp_ranks_faults_come_out_incorrect(dp_cell, fault):
    res = dp_ranks.run(dp_cell, 99, 0.5, False, platform="cpu", fault=fault)
    assert not res["correct"], res["compared"]


def test_dp_control_and_planted_faults_fail_the_comparison(dp_cell):
    harness.claim_device(1, "cpu")
    exact = _train.reference_steps(dp_cell, 3, "f32")
    for kw in (dict(precision="bf16"), dict(precision="f32", fault="half_batch"),
               dict(precision="f32", fault="no_exchange")):
        readings, _ = compare.train(_train.reference_steps(dp_cell, 3, **kw), exact)
        assert not compare.judge(readings, dp_cell["limits"])[0], (kw, readings)
        if "fault" not in kw:  # the number that is there for the control fails it alone
            assert readings["grad_diff"] > dp_cell["limits"]["grad_diff"]


def test_twin_has_the_real_models_tree(dp_cell):
    """Names, shapes and dtypes of the twin's gradient tree are VGG16's, at
    the published size, and its gradient is minus half its input."""
    import jax.numpy as jnp
    import optax

    cfg = harness.load("configs", "vgg16")
    real = _models.build(cfg, {})
    shapes = _models.program_shapes(
        real, jax.ShapeDtypeStruct((1, 224, 224, 3), jnp.float32))
    flat = {p: (tuple(s.shape), s.dtype) for p, s in
            weights.flatten(shapes).items()}
    assert sum(int(np.prod(s)) for s, _ in flat.values()) == 138_357_544
    t = twin.Twin(twin.children_of(shapes))
    got = jax.eval_shape(lambda k: t.init(k, {p: jax.ShapeDtypeStruct(s, jnp.float32)
                                              for p, (s, _) in flat.items()}),
                         jax.random.PRNGKey(0))["params"]["tree"]
    got = {p: (tuple(s.shape), s.dtype) for p, s in
           weights.flatten(_models._plain(got)).items()}
    assert got == flat
    # the gradient, on a small tree, through the trainer's own loss
    small = {"a": {"kernel": jax.ShapeDtypeStruct((3, 4), jnp.float32),
                   "bias": jax.ShapeDtypeStruct((4,), jnp.float32)}}
    t = twin.Twin(twin.children_of(small))
    inputs = {"a/kernel": twin.pattern(5, "a/kernel", (3, 4), 0.7),
              "a/bias": twin.pattern(5, "a/bias", (4,), 0.7)}
    params = t.init(jax.random.PRNGKey(0), inputs)["params"]
    params = jax.tree.map(lambda x: x + 0.3, params)  # whatever the parameters are

    def loss(p):
        out = t.apply({"params": p}, inputs, train=True)
        return optax.softmax_cross_entropy_with_integer_labels(
            out, jnp.zeros((1,), jnp.int32)).mean()

    g = jax.grad(loss)(params)["tree"]["a"]
    np.testing.assert_allclose(g["kernel"], twin.grad_leaf(5, "a/kernel", (3, 4), 0.7), rtol=1e-6)
    assert float(jnp.linalg.norm(g["bias"])) == pytest.approx(0.7, rel=0.5)
