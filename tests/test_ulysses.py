"""Ulysses (all-to-all) sequence parallelism parity — both tiers.

ICI tier: `ulysses_self_attention` on the virtual CPU mesh vs full attention.
DCN tier: `dcn_ulysses_attention` across real processes over the transport's
native AllToAll, vs the single-host reference sliced to each rank's shard.
"""

from __future__ import annotations

import os

import numpy as np
import pytest

# Module level so mp-spawn children (which re-import this module, but not
# conftest.py) are held to the CPU too.
os.environ["JAX_PLATFORMS"] = "cpu"
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp  # noqa: E402

from conftest import run_spawn_workers  # noqa: E402

from tpunet.ops import attention_reference  # noqa: E402
from tpunet.parallel import make_named_mesh, ulysses_self_attention  # noqa: E402


def _qkv(rng, b, s, h, d, dtype=jnp.float32):
    ks = jax.random.split(rng, 3)
    return tuple(jax.random.normal(k, (b, s, h, d), dtype) for k in ks)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_full_attention(causal):
    mesh = make_named_mesh({"dp": 2, "sp": 4})
    q, k, v = _qkv(jax.random.PRNGKey(0), 4, 32, 4, 8)  # heads % sp == 0
    out = ulysses_self_attention(q, k, v, mesh, causal=causal)
    ref = attention_reference(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_ulysses_with_tp_heads():
    # Heads split over tp, then further over sp by the all-to-all.
    mesh = make_named_mesh({"dp": 2, "sp": 2, "tp": 2})
    q, k, v = _qkv(jax.random.PRNGKey(1), 2, 16, 4, 8)
    out = ulysses_self_attention(q, k, v, mesh, causal=True, tp_axis="tp")
    ref = attention_reference(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_ulysses_grad_matches():
    mesh = make_named_mesh({"sp": 4})
    q, k, v = _qkv(jax.random.PRNGKey(3), 2, 32, 4, 8)

    def loss_u(q, k, v):
        return jnp.sum(ulysses_self_attention(q, k, v, mesh, causal=True, dp_axis=None) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, True) ** 2)

    gu = jax.grad(loss_u, argnums=(0, 1, 2))(q, k, v)
    gref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gu, gref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5, rtol=5e-5)


def test_ulysses_head_divisibility_error():
    mesh = make_named_mesh({"sp": 8})
    q, k, v = _qkv(jax.random.PRNGKey(4), 1, 32, 4, 8)  # 4 heads, sp=8
    with pytest.raises(ValueError, match="divisible"):
        ulysses_self_attention(q, k, v, mesh, dp_axis=None)


# -- DCN tier ---------------------------------------------------------------

B, S, H, D = 2, 32, 4, 8


def _full_qkv():
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    return tuple(jax.random.normal(k, (B, S, H, D), jnp.float32) for k in ks)


def _worker(rank: int, world: int, port: int, q, causal: bool) -> None:
    try:
        import jax

        jax.config.update("jax_platforms", "cpu")

        from tpunet import distributed
        from tpunet.ops import attention_reference
        from tpunet.parallel import dcn_ulysses_attention

        distributed.initialize(f"127.0.0.1:{port}", rank, world)
        qf, kf, vf = _full_qkv()  # same on every rank (same seed)
        s_local = S // world
        sl = slice(rank * s_local, (rank + 1) * s_local)

        fn = jax.jit(lambda a, b, c: dcn_ulysses_attention(a, b, c, causal=causal))
        got = fn(qf[:, sl], kf[:, sl], vf[:, sl])

        want = attention_reference(qf, kf, vf, causal)[:, sl]
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5
        )
        distributed.finalize()
        q.put((rank, "OK"))
    except Exception as e:  # noqa: BLE001
        q.put((rank, f"FAIL: {type(e).__name__}: {e}"))


@pytest.mark.parametrize("causal", [False, True])
def test_dcn_ulysses_2proc(causal):
    run_spawn_workers(_worker, 2, extra_args=(causal,))


def test_dcn_ulysses_4proc_causal():
    run_spawn_workers(_worker, 4, extra_args=(True,))


def _model_worker(rank: int, world: int, port: int, q) -> None:
    # Full Transformer with attn_impl="dcn_ulysses": each rank's logits on
    # its sequence shard must equal the single-host reference model's logits
    # sliced to that shard (global rotary + full-sequence causality).
    try:
        import jax

        jax.config.update("jax_platforms", "cpu")
        import jax.numpy as jnp

        from tpunet import distributed
        from tpunet.models import Transformer

        distributed.initialize(f"127.0.0.1:{port}", rank, world)
        kw = dict(vocab=32, d_model=16, n_layers=2, n_heads=2, d_ff=32,
                  compute_dtype=jnp.float32)
        ref_model = Transformer(attn_impl="reference", **kw)
        uly_model = Transformer(attn_impl="dcn_ulysses", **kw)

        toks = jax.random.randint(jax.random.PRNGKey(3), (2, S), 0, 32)
        params = ref_model.init(jax.random.PRNGKey(4), toks)["params"]
        want = ref_model.apply({"params": params}, toks)

        s_local = S // world
        sl = slice(rank * s_local, (rank + 1) * s_local)
        got = uly_model.apply({"params": params}, toks[:, sl])
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want[:, sl]), atol=1e-4, rtol=1e-4
        )
        distributed.finalize()
        q.put((rank, "OK"))
    except Exception as e:  # noqa: BLE001
        q.put((rank, f"FAIL: {type(e).__name__}: {e}"))


def test_transformer_dcn_ulysses_2proc():
    run_spawn_workers(_model_worker, 2)
