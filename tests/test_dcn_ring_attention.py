"""Cross-host (multi-process) ring attention parity vs full attention."""

from __future__ import annotations

import os

import numpy as np
import pytest

# Module level so mp-spawn children (which re-import this module, but not
# conftest.py) are held to the CPU too.
os.environ["JAX_PLATFORMS"] = "cpu"
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

from conftest import run_spawn_workers  # noqa: E402

B, S, H, D = 2, 32, 2, 8  # full (unsharded) attention problem


def _full_qkv():
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    return tuple(jax.random.normal(k, (B, S, H, D), jnp.float32) for k in ks)


def _worker(rank: int, world: int, port: int, q, causal: bool) -> None:
    try:
        import jax

        jax.config.update("jax_platforms", "cpu")
        import jax.numpy as jnp

        from tpunet import distributed
        from tpunet.ops import attention_reference
        from tpunet.parallel import dcn_ring_attention

        distributed.initialize(f"127.0.0.1:{port}", rank, world)
        qf, kf, vf = _full_qkv()  # same on every rank (same seed)
        s_local = S // world
        sl = slice(rank * s_local, (rank + 1) * s_local)

        fn = jax.jit(lambda a, b, c: dcn_ring_attention(a, b, c, causal=causal))
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
def test_dcn_ring_attention_2proc(causal):
    run_spawn_workers(_worker, 2, extra_args=(causal,))


def test_dcn_ring_attention_4proc_causal():
    run_spawn_workers(_worker, 4, extra_args=(True,))


def _model_worker(rank: int, world: int, port: int, q) -> None:
    # Full Transformer with sequence sharded across processes: each rank's
    # logits on its shard must equal the single-host reference model's
    # logits sliced to that shard (global rotary + ring causality).
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
        dcn_model = Transformer(attn_impl="dcn_ring", **kw)

        toks = jax.random.randint(jax.random.PRNGKey(3), (2, S), 0, 32)
        params = ref_model.init(jax.random.PRNGKey(4), toks)["params"]
        want = ref_model.apply({"params": params}, toks)

        s_local = S // world
        sl = slice(rank * s_local, (rank + 1) * s_local)
        got = dcn_model.apply({"params": params}, toks[:, sl])
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want[:, sl]), atol=1e-4, rtol=1e-4
        )
        distributed.finalize()
        q.put((rank, "OK"))
    except Exception as e:  # noqa: BLE001
        q.put((rank, f"FAIL: {type(e).__name__}: {e}"))


def test_transformer_dcn_ring_2proc():
    run_spawn_workers(_model_worker, 2)


def _zigzag_worker(rank: int, world: int, port: int, q) -> None:
    # Balanced cross-host layout: rank holds chunks (rank, 2W-1-rank) of the
    # zigzag-permuted sequence; gathered outputs un-permute to the full
    # causal reference.
    try:
        import jax

        jax.config.update("jax_platforms", "cpu")

        from tpunet import distributed
        from tpunet.ops import attention_reference
        from tpunet.parallel import dcn_zigzag_attention, to_zigzag

        distributed.initialize(f"127.0.0.1:{port}", rank, world)
        qf, kf, vf = _full_qkv()
        qz, kz, vz = (to_zigzag(x, world) for x in (qf, kf, vf))
        s_local = S // world
        sl = slice(rank * s_local, (rank + 1) * s_local)

        fn = jax.jit(dcn_zigzag_attention)
        got = fn(qz[:, sl], kz[:, sl], vz[:, sl])

        want = to_zigzag(attention_reference(qf, kf, vf, True), world)[:, sl]
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5
        )
        distributed.finalize()
        q.put((rank, "OK"))
    except Exception as e:  # noqa: BLE001
        q.put((rank, f"FAIL: {type(e).__name__}: {e}"))


def test_dcn_zigzag_2proc():
    run_spawn_workers(_zigzag_worker, 2)


def test_dcn_zigzag_4proc():
    run_spawn_workers(_zigzag_worker, 4)


def _zigzag_model_worker(rank: int, world: int, port: int, q) -> None:
    # Full Transformer with attn_impl="dcn_zigzag": each rank's logits on its
    # zigzag shard must equal the single-host reference model's logits,
    # zigzag-permuted and sliced to that shard (rotary uses zigzag_positions).
    try:
        import jax

        jax.config.update("jax_platforms", "cpu")
        import jax.numpy as jnp

        from tpunet import distributed
        from tpunet.models import Transformer
        from tpunet.parallel import to_zigzag

        distributed.initialize(f"127.0.0.1:{port}", rank, world)
        kw = dict(vocab=32, d_model=16, n_layers=2, n_heads=2, d_ff=32,
                  compute_dtype=jnp.float32)
        ref_model = Transformer(attn_impl="reference", **kw)
        zz_model = Transformer(attn_impl="dcn_zigzag", **kw)

        seq = 32
        toks = jax.random.randint(jax.random.PRNGKey(5), (2, seq), 0, 32)
        params = ref_model.init(jax.random.PRNGKey(0), toks)["params"]
        want = to_zigzag(ref_model.apply({"params": params}, toks), world)

        s_local = seq // world
        sl = slice(rank * s_local, (rank + 1) * s_local)
        toks_zz = to_zigzag(toks, world)
        got = zz_model.apply({"params": params}, toks_zz[:, sl])
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want[:, sl]), atol=3e-5, rtol=3e-5
        )
        distributed.finalize()
        q.put((rank, "OK"))
    except Exception as e:  # noqa: BLE001
        q.put((rank, f"FAIL: {type(e).__name__}: {e}"))


def test_dcn_zigzag_transformer_2proc():
    run_spawn_workers(_zigzag_model_worker, 2)
