"""XLA FFI custom-call collectives (round 5): the zero-copy CPU path.

dcn_all_reduce lowers to a native XLA custom call on the CPU backend
(cpp/src/xla_ffi.cc) instead of the io_callback host bridge — same
semantics, no host staging copies. These tests pin: path activation,
multi-tensor ordering across ranks, dtype coverage, the elastic
communicator swap under an already-compiled executable, and the
io_callback fallback when the path is disabled.
"""

from __future__ import annotations

import os

import numpy as np

os.environ["JAX_PLATFORMS"] = "cpu"
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402

from conftest import free_port, run_spawn_workers  # noqa: E402


def _ffi_present() -> bool:
    from tpunet import _native

    return hasattr(_native.load(), "TpunetFfiAllReduce")


pytestmark = pytest.mark.skipif(
    not _ffi_present(),
    reason="libtpunet.so built without jaxlib FFI headers")


def test_ffi_path_is_active_on_cpu():
    from tpunet.interop import _ffi_available

    assert _ffi_available()


def test_ffi_lowering_contains_custom_call():
    # The jitted psum must lower to the custom call, not the host callback.
    from tpunet import distributed
    from tpunet.interop import dcn_psum

    distributed.finalize()
    distributed.initialize(f"127.0.0.1:{free_port()}", 0, 1)
    try:
        txt = jax.jit(dcn_psum).lower(jnp.ones((4,), jnp.float32)).as_text()
        assert "tpunet_all_reduce" in txt
        assert "io_callback" not in txt
    finally:
        distributed.finalize()


def test_ffi_dtypes_and_zero_size_world1():
    import ml_dtypes

    from tpunet import distributed
    from tpunet.interop import dcn_psum

    distributed.finalize()
    distributed.initialize(f"127.0.0.1:{free_port()}", 0, 1)
    try:
        for dt in (jnp.float32, jnp.int32, ml_dtypes.bfloat16, jnp.uint8):
            x = jnp.arange(7).astype(dt)
            y = jax.jit(dcn_psum)(x)
            np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
        # f64/i64 need x64 mode or they silently downcast to f32/i32 and
        # dtype codes 1/4 would never be exercised.
        with jax.enable_x64():
            for dt in (jnp.float64, jnp.int64):
                x = jnp.arange(7).astype(dt)
                assert x.dtype == dt
                y = jax.jit(dcn_psum)(x)
                np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
        z = jax.jit(dcn_psum)(jnp.zeros((0,), jnp.float32))
        assert z.shape == (0,)
    finally:
        distributed.finalize()


def test_ffi_elastic_comm_swap_under_compiled_executable():
    # THE elastic guarantee: the executable caches no communicator id —
    # the handler resolves the process default at call time, so replacing
    # the communicator (recovery) under an already-compiled step works.
    from tpunet import distributed
    from tpunet.interop import dcn_psum

    distributed.finalize()
    fn = jax.jit(dcn_psum)
    x = jnp.arange(5, dtype=jnp.float32)

    distributed.initialize(f"127.0.0.1:{free_port()}", 0, 1)
    np.testing.assert_array_equal(np.asarray(fn(x)), np.asarray(x))
    distributed.finalize()

    # Destroyed comm must fail loudly, not dereference a dead id.
    with pytest.raises(Exception, match="default communicator|initialize"):
        fn(x).block_until_ready()

    distributed.initialize(f"127.0.0.1:{free_port()}", 0, 1)  # NEW comm
    try:
        np.testing.assert_array_equal(np.asarray(fn(x)), np.asarray(x))
    finally:
        distributed.finalize()


def test_ffi_disabled_falls_back_to_io_callback():
    from tpunet import distributed
    from tpunet.interop import dcn_psum

    distributed.finalize()
    distributed.initialize(f"127.0.0.1:{free_port()}", 0, 1)
    old = os.environ.get("TPUNET_FFI_COLLECTIVES")
    os.environ["TPUNET_FFI_COLLECTIVES"] = "0"
    # The flag is read at TRACE time and traces are cached per function
    # object — drop them so the toggle actually re-lowers (process-level
    # config; mid-process toggling is a test-only move).
    jax.clear_caches()
    try:
        txt = jax.jit(dcn_psum).lower(jnp.ones((4,), jnp.float32)).as_text()
        assert "tpunet_all_reduce" not in txt
        x = jnp.arange(4, dtype=jnp.float32)
        np.testing.assert_array_equal(np.asarray(jax.jit(dcn_psum)(x)),
                                      np.asarray(x))
    finally:
        if old is None:
            del os.environ["TPUNET_FFI_COLLECTIVES"]
        else:
            os.environ["TPUNET_FFI_COLLECTIVES"] = old
        jax.clear_caches()
        distributed.finalize()


def _ordering_worker(rank: int, world: int, port: int, q) -> None:
    # Several independent FFI collectives inside ONE jit: the compiled
    # schedule must issue them in the same order on every rank (identical
    # HLO -> deterministic schedule), or the single-threaded ring comm
    # would cross-match different collectives and corrupt/deadlock.
    try:
        import jax

        jax.config.update("jax_platforms", "cpu")
        import jax.numpy as jnp

        from tpunet import distributed
        from tpunet.interop import dcn_all_reduce, dcn_pmean, dcn_psum

        distributed.initialize(f"127.0.0.1:{port}", rank, world)

        a = jnp.full((64,), float(rank + 1), jnp.float32)
        b = jnp.arange(33, dtype=jnp.float32) * (rank + 1)
        c = jnp.full((7,), rank + 1, jnp.int32)

        @jax.jit
        def mixed(a, b, c):
            s1 = dcn_psum(a)                      # f32
            s2 = dcn_all_reduce(b, "max")         # f32 max
            s3 = dcn_psum(c.astype(jnp.float32))  # converted
            s4 = dcn_pmean(a * 2.0)
            return s1, s2, s3, s4

        for _ in range(3):  # repeat: the schedule must be stable run-to-run
            s1, s2, s3, s4 = mixed(a, b, c)
            tot = sum(range(1, world + 1))
            np.testing.assert_allclose(np.asarray(s1), np.full(64, tot),
                                       rtol=1e-6)
            np.testing.assert_allclose(
                np.asarray(s2), np.arange(33, dtype=np.float32) * world,
                rtol=1e-6)
            np.testing.assert_allclose(np.asarray(s3), np.full(7, tot),
                                       rtol=1e-6)
            np.testing.assert_allclose(
                np.asarray(s4), np.full(64, 2.0 * tot / world), rtol=1e-6)

        # Gradient through the FFI custom call (custom_vjp wraps it).
        g = jax.grad(lambda v: dcn_psum(v).sum())(a)
        np.testing.assert_array_equal(np.asarray(g),
                                      np.full(64, float(world)))

        # Every OTHER FFI collective in one jit, interleaved — the full
        # zoo must stay order-coherent across ranks too.
        from tpunet.interop import (dcn_all_gather, dcn_all_to_all,
                                    dcn_broadcast, dcn_neighbor_exchange,
                                    dcn_reduce_scatter)

        v = jnp.arange(2 * world * 3, dtype=jnp.float32).reshape(
            2 * world, 3) * (rank + 1)

        @jax.jit
        def zoo(v):
            g1 = dcn_all_gather(v[0])            # (world, 3)
            rs = dcn_reduce_scatter(v)           # (2, 3) summed shard
            bc = dcn_broadcast(v[1], root=0)
            ne = dcn_neighbor_exchange(v[2])
            a2a = dcn_all_to_all(v[:world])
            return g1, rs, bc, ne, a2a

        g1, rs, bc, ne, a2a = zoo(v)
        base = np.arange(2 * world * 3, dtype=np.float32).reshape(
            2 * world, 3)
        np.testing.assert_allclose(
            np.asarray(g1), np.stack([base[0] * (r + 1)
                                      for r in range(world)]))
        tot = sum(range(1, world + 1))
        np.testing.assert_allclose(
            np.asarray(rs), base[2 * rank: 2 * rank + 2] * tot)
        np.testing.assert_allclose(np.asarray(bc), base[1] * 1.0)  # root 0
        prev = (rank - 1 + world) % world
        np.testing.assert_allclose(np.asarray(ne), base[2] * (prev + 1))
        np.testing.assert_allclose(
            np.asarray(a2a), np.stack([base[rank] * (r + 1)
                                       for r in range(world)]))

        distributed.finalize()
        q.put((rank, "OK"))
    except Exception as e:  # noqa: BLE001
        q.put((rank, f"FAIL: {type(e).__name__}: {e}"))


def test_ffi_multi_tensor_ordering_3proc():
    run_spawn_workers(_ordering_worker, 3)


def test_ffi_error_is_classified_as_comm_failure():
    # The handler mirrors NativeError's "tpunet native <op> failed" text so
    # elastic recovery's is_comm_failure string-match keeps working when
    # the failure surfaces as XlaRuntimeError from the custom call.
    from tpunet import distributed
    from tpunet.interop import _ffi_available
    from tpunet.train.elastic import is_comm_failure

    distributed.finalize()
    distributed.initialize(f"127.0.0.1:{free_port()}", 0, 1)
    try:
        assert _ffi_available()
        bad = jax.ffi.ffi_call(
            "tpunet_all_reduce",
            jax.ShapeDtypeStruct((4,), jnp.float32), has_side_effect=True)
        with pytest.raises(Exception) as ei:
            bad(jnp.ones((4,), jnp.float32),
                dtype=np.int64(99), op=np.int64(0))  # invalid dtype code
        assert is_comm_failure(ei.value), str(ei.value)
    finally:
        distributed.finalize()


def test_ffi_every_target_in_lowering():
    # Each dcn_* must lower to ITS custom call on the CPU backend — a
    # silent fall-through to io_callback on any one op would quietly
    # reintroduce the 3-copy bridge tax there.
    from tpunet import distributed
    from tpunet.interop import (dcn_all_gather, dcn_all_to_all,
                                dcn_broadcast, dcn_neighbor_exchange,
                                dcn_psum, dcn_reduce_scatter)

    distributed.finalize()
    distributed.initialize(f"127.0.0.1:{free_port()}", 0, 1)
    try:
        x = jnp.ones((4, 2), jnp.float32)
        for fn, target in (
            (dcn_psum, "tpunet_all_reduce"),
            (dcn_all_gather, "tpunet_all_gather"),
            (dcn_reduce_scatter, "tpunet_reduce_scatter"),
            (dcn_broadcast, "tpunet_broadcast"),
            (dcn_neighbor_exchange, "tpunet_neighbor_exchange"),
        ):
            txt = jax.jit(fn).lower(x).as_text()
            assert target in txt, (target, txt[:500])
        txt = jax.jit(dcn_all_to_all).lower(
            jnp.ones((1, 4), jnp.float32)).as_text()
        assert "tpunet_all_to_all" in txt
    finally:
        distributed.finalize()


def _asymmetric_chain_worker(rank: int, world: int, port: int, q) -> None:
    # Rank-ASYMMETRIC trace (rank-dependent constants baked in) issuing two
    # data-independent neighbor exchanges: exactly the pattern that
    # cross-matched on the FFI path in dcn_ring_attention (round-5 bug).
    # after=(ea,) makes ea an operand of the second custom call, pinning
    # the order (optimization_barrier demonstrably does NOT); the
    # packed-exchange alternative is covered by test_dcn_ring_attention.
    try:
        import jax

        jax.config.update("jax_platforms", "cpu")
        import jax.numpy as jnp

        from tpunet import distributed
        from tpunet.interop import dcn_neighbor_exchange

        distributed.initialize(f"127.0.0.1:{port}", rank, world)

        a = jnp.full((32,), 10.0 * (rank + 1), jnp.float32)
        b = jnp.full((32,), 100.0 * (rank + 1), jnp.float32)

        @jax.jit
        def ring_like(a, b):
            # rank-dependent constant makes per-rank HLO differ
            a = a + float(rank)
            ea = dcn_neighbor_exchange(a)
            eb = dcn_neighbor_exchange(b, after=(ea,))
            return ea, eb

        for _ in range(3):
            ea, eb = ring_like(a, b)
            prev = (rank - 1 + world) % world
            np.testing.assert_allclose(
                np.asarray(ea), np.full(32, 10.0 * (prev + 1) + prev))
            np.testing.assert_allclose(
                np.asarray(eb), np.full(32, 100.0 * (prev + 1)))
        distributed.finalize()
        q.put((rank, "OK"))
    except Exception as e:  # noqa: BLE001
        q.put((rank, f"FAIL: {type(e).__name__}: {e}"))


def test_ffi_rank_asymmetric_trace_with_after_kwarg_4proc():
    run_spawn_workers(_asymmetric_chain_worker, 4)
