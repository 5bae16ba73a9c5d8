"""JAX ↔ tpunet interop tests: numeric parity of DCN collectives vs
`jax.lax` ground truth, inside jit, including gradients.
"""

from __future__ import annotations

import os

import numpy as np
import pytest

# Module level so mp-spawn children (which re-import this module, but not
# conftest.py) are held to the virtual CPU mesh too.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

from conftest import free_port, run_spawn_workers  # noqa: E402


def _rank_arr(rank: int, n: int = 4096) -> np.ndarray:
    rng = np.random.default_rng(100 + rank)
    return rng.standard_normal(n).astype(np.float32)


def test_world1_psum_identity_and_grad():
    import jax
    import jax.numpy as jnp

    from tpunet import distributed
    from tpunet.interop import dcn_all_gather, dcn_psum

    distributed.finalize()
    distributed.initialize(f"127.0.0.1:{free_port()}", 0, 1)
    x = jnp.asarray(_rank_arr(0))

    y = jax.jit(dcn_psum)(x)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x))

    g = jax.grad(lambda v: dcn_psum(v).sum())(x)
    np.testing.assert_array_equal(np.asarray(g), np.ones_like(x))

    gathered = jax.jit(dcn_all_gather)(x)
    assert gathered.shape == (1,) + x.shape
    distributed.finalize()


def _psum_worker(rank: int, world: int, port: int, q) -> None:
    try:
        import jax
        import jax.numpy as jnp

        from tpunet import distributed
        from tpunet.interop import (
            dcn_all_gather,
            dcn_pmean,
            dcn_psum,
            dcn_reduce_scatter,
        )

        distributed.initialize(f"127.0.0.1:{port}", rank, world)
        x = jnp.asarray(_rank_arr(rank))

        # psum under jit vs numpy ground truth.
        y = jax.jit(dcn_psum)(x)
        expect = sum(_rank_arr(r) for r in range(world))
        np.testing.assert_allclose(np.asarray(y), expect, rtol=1e-5, atol=1e-5)

        # pmean.
        m = jax.jit(dcn_pmean)(x)
        np.testing.assert_allclose(np.asarray(m), expect / world, rtol=1e-5, atol=1e-5)

        # gradient of sum(psum(x)): cotangent all-reduced -> world * ones.
        g = jax.jit(jax.grad(lambda v: dcn_psum(v).sum()))(x)
        np.testing.assert_allclose(np.asarray(g), world * np.ones_like(x), rtol=1e-6)

        # all_gather parity.
        ag = jax.jit(dcn_all_gather)(x)
        for r in range(world):
            np.testing.assert_array_equal(np.asarray(ag)[r], _rank_arr(r))

        # reduce_scatter parity.
        rs = jax.jit(dcn_reduce_scatter)(x)
        shard = 4096 // world
        np.testing.assert_allclose(
            np.asarray(rs), expect[rank * shard : (rank + 1) * shard], rtol=1e-5, atol=1e-5
        )

        # non-sum reduction op.
        from tpunet.interop import dcn_all_reduce

        mx = jax.jit(lambda v: dcn_all_reduce(v, "max"))(x)
        np.testing.assert_array_equal(
            np.asarray(mx), np.max([_rank_arr(r) for r in range(world)], axis=0)
        )

        # broadcast from the last rank.
        from tpunet.interop import dcn_barrier, dcn_broadcast, dcn_neighbor_exchange

        root = world - 1
        payload = x if rank == root else jnp.zeros_like(x)
        bc = jax.jit(lambda v: dcn_broadcast(v, root))(payload)
        np.testing.assert_array_equal(np.asarray(bc), _rank_arr(root))

        # neighbor exchange: get prev rank's array.
        ne = jax.jit(dcn_neighbor_exchange)(x)
        np.testing.assert_array_equal(np.asarray(ne), _rank_arr((rank - 1 + world) % world))

        dcn_barrier()

        q.put((rank, "OK"))
    except Exception as e:  # noqa: BLE001
        q.put((rank, f"FAIL: {type(e).__name__}: {e}"))


def test_two_process_psum_parity_vs_lax():
    """2 processes run dcn collectives; the parent independently computes
    `jax.lax.psum` over a 2-device CPU mesh on the same per-rank arrays and
    the results must match."""
    import jax
    import jax.numpy as jnp

    world = 2
    run_spawn_workers(_psum_worker, world)

    # lax.psum ground truth over 2 virtual CPU devices (same math XLA would
    # run in-pod): stacking both ranks' arrays and psumming over the device
    # axis must equal what the DCN ring produced (checked in-worker vs the
    # same numpy expectation).
    stacked = jnp.stack([jnp.asarray(_rank_arr(r)) for r in range(world)])
    lax_result = jax.pmap(lambda v: jax.lax.psum(v, "i"), axis_name="i")(stacked)
    expect = sum(_rank_arr(r) for r in range(world))
    np.testing.assert_allclose(np.asarray(lax_result[0]), expect, rtol=1e-5, atol=1e-5)


def test_psum_requires_initialize():
    import jax.numpy as jnp

    from tpunet import distributed
    from tpunet.interop import dcn_psum

    distributed.finalize()
    with pytest.raises(RuntimeError, match="initialize"):
        dcn_psum(jnp.ones(4))


def test_two_communicator_async_registry_no_collision():
    """Two live Communicators issue native tickets that both count from 1;
    the pending-async registry must key by (comm, ticket) so interleaved
    start/finish pairs resolve to the right communicator's buffer."""
    from tpunet.collectives import Communicator
    from tpunet.interop import _pop_pending, _register_pending, dcn_async_stats

    comm_a = Communicator(f"127.0.0.1:{free_port()}", 0, 1)
    comm_b = Communicator(f"127.0.0.1:{free_port()}", 0, 1)
    try:
        xa = _rank_arr(0)
        xb = -2.0 * _rank_arr(0)
        ta = _register_pending(comm_a, comm_a.iall_reduce(xa.copy()))
        tb = _register_pending(comm_b, comm_b.iall_reduce(xb.copy()))
        # Native tickets are per-comm sequential: identical numerically.
        assert ta == tb
        assert dcn_async_stats()["in_flight"] >= 2
        # Finish in reverse order; each must get its own comm's data.
        np.testing.assert_array_equal(_pop_pending(comm_b, tb).wait(), xb)
        np.testing.assert_array_equal(_pop_pending(comm_a, ta).wait(), xa)
        # A finish against the wrong comm (stale ticket) fails loudly.
        import pytest as _pytest

        with _pytest.raises(RuntimeError, match="no pending async"):
            _pop_pending(comm_a, ta)
    finally:
        comm_a.close()
        comm_b.close()


def _ticket_after_worker(rank: int, world: int, port: int, q) -> None:
    """`after=` threads through the TICKET API: the start/finish callbacks
    become consumers of earlier FFI results (and the ticket/finish result
    are legal FFI `after=` operands), so a rank-asymmetric trace can bridge
    the two ordering machineries by data flow instead of reading a
    documented hazard."""
    try:
        import jax
        import jax.numpy as jnp

        from tpunet import distributed
        from tpunet.interop import (
            dcn_all_gather,
            dcn_all_reduce,
            dcn_all_reduce_finish,
            dcn_all_reduce_start,
        )

        distributed.initialize(f"127.0.0.1:{port}", rank, world)
        x = jnp.asarray(_rank_arr(rank, 1024))

        def prog(v):
            a = dcn_all_reduce(v, "sum")                    # FFI path
            t = dcn_all_reduce_start(2.0 * v, after=(a,))   # pinned after a
            g = dcn_all_gather(v, after=(t,))               # pinned after start
            r = dcn_all_reduce_finish(t, v, after=(g,))     # pinned after gather
            return a, g, r

        a, g, r = jax.jit(prog)(x)
        expect = sum(_rank_arr(s, 1024) for s in range(world))
        np.testing.assert_allclose(np.asarray(a), expect, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(r), 2.0 * expect, rtol=1e-5,
                                   atol=1e-5)
        for s in range(world):
            np.testing.assert_array_equal(np.asarray(g)[s], _rank_arr(s, 1024))
        q.put((rank, "OK"))
    except Exception as e:  # noqa: BLE001
        q.put((rank, f"FAIL: {type(e).__name__}: {e}"))


def test_ticket_after_bridges_ffi_ordering():
    run_spawn_workers(_ticket_after_worker, 2)
