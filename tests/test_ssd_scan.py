"""The chunked state-space scan's kernels (tpunet/ops/ssd_scan.py, in
Pallas' interpreter) against the token-by-token recurrence of the plain
reference (perfbench/references/nemotron_h.py): forward and every
gradient, over whole chunks, a ragged tail and heads whose state outlives
several chunks."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.references import nemotron_h as ref
from tpunet.ops.ssd_scan import ssd_scan


def _close(got, want, tol):
    scale = float(jnp.max(jnp.abs(want))) + 1e-6
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=tol * scale)


def _recurrence(x, dt, a, b, c):
    """The reference's token-by-token scan, a row at a time."""
    per = x.shape[2] // b.shape[2]
    heads = lambda t: jnp.repeat(t, per, axis=2)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        return jnp.stack([ref.ssm_scan(x[i], dt[i], a, heads(b)[i], heads(c)[i], "f32")
                          for i in range(x.shape[0])])


def _scan_inputs(seq, dt_bias, a_log, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    rows, heads, p, groups, n = 2, 4, 8, 2, 16
    dt = jax.nn.softplus(jax.random.normal(k[1], (rows, seq, heads)) + jnp.asarray(dt_bias))
    return (jax.random.normal(k[0], (rows, seq, heads, p)), dt, -jnp.exp(jnp.asarray(a_log)),
            jax.random.normal(k[2], (rows, seq, groups, n)),
            jax.random.normal(k[3], (rows, seq, groups, n)))


# seq: several whole chunks; one that is not a multiple of the chunk. The
# decays: heads that forget within a chunk beside ones whose state outlives
# several (per-token decay above 0.99 at dt_bias -6).
CASES = {"four_chunks": (64, [-1.0, 0.0, 0.5, 1.0], [0.0, 0.5, 1.0, 2.0]),
         "ragged": (45, [-1.0, 0.0, 0.5, 1.0], [0.0, 0.5, 1.0, 2.0]),
         "long_memory": (64, [-6.0, -7.0, -5.0, -1.0], [-1.0, -0.5, 0.0, 0.0])}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_scan_kernels_match_the_recurrence(case):
    args = _scan_inputs(*CASES[case])
    with jax.default_matmul_precision("highest"):
        got = ssd_scan(*args, 16)
    want = _recurrence(*args)
    _close(got, want, 1e-5)
    probe = jax.random.normal(jax.random.PRNGKey(9), want.shape)
    mine = jax.jit(jax.grad(lambda *a: jnp.sum(ssd_scan(*a, 16) * probe),
                            argnums=range(5)))(*args)
    theirs = jax.jit(jax.grad(lambda *a: jnp.sum(_recurrence(*a) * probe),
                              argnums=range(5)))(*args)
    for name, g, w in zip(("x", "dt", "a", "b", "c"), mine, theirs):
        assert g.shape == w.shape, name
        _close(g, w, 1e-4)


def test_the_state_crosses_chunks():
    """On heads whose decay spans chunks, a scan whose chunks start from a
    zero state (the fault `state_dropped` plants) is far from the
    recurrence, and the kernels are not."""
    x, dt, a, b, c = _scan_inputs(*CASES["long_memory"])
    want = _recurrence(x, dt, a, b, c)
    cut = lambda t: t.reshape(t.shape[0] * 4, 16, *t.shape[2:])  # noqa: E731
    dropped = ssd_scan(cut(x), cut(dt), a, cut(b), cut(c), 16).reshape(want.shape)
    err = float(jnp.max(jnp.abs(dropped - want))) / float(jnp.max(jnp.abs(want)))
    assert err > 0.1
    assert float(jnp.mean(jnp.exp(dt[..., 0] * a[0]))) > 0.99


def test_the_compiled_scan_wants_whole_lanes():
    with pytest.raises(ValueError, match="multiple of 128"):
        ssd_scan(*_scan_inputs(64, [0.0] * 4, [0.0] * 4), 16, interpret=False)
