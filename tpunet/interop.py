"""JAX ↔ tpunet interop: cross-host collectives inside jitted programs,
and one between them.

A collective that has a program boundary to stand at does not enter a
program at all: `host_all_reduce` takes a device array to the host by the
runtime's own array transfer, reduces it over the ring and puts the result
back, from the calling thread, in chunks that are in different stages at
one time. The trainer's flat gradient exchange
(make_train_step(cross_host=True): between backward and the optimizer) is
that case and uses nothing else of this module. The in-jit seam below is for
collectives in the MIDDLE of a program — ZeRO's reduce-scatter and
all-gather, the bucketed start/finish tickets, hierarchical_psum under
shard_map, ring and zigzag attention — which cannot leave it.

XLA has no NCCL-style net-plugin seam (SURVEY §7 hard-part #1), so the
cross-host path enters jitted code two ways:

- **XLA FFI custom call** (CPU backend, default): the native handler
  (cpp/src/xla_ffi.cc) receives the XLA buffers DIRECTLY — the ring
  communicator reads the operand and writes the result in place, zero
  host staging. Measured round 5 at 128 MiB/W=2: the io_callback bridge
  alone (identity callback, no reduce) costs 0.48 s — about three
  full-buffer copies — on top of the 0.24 s native reduce; the FFI path
  removes all of it. The communicator is resolved at CALL time through
  the process-default registry, so elastic recovery re-points it under
  already-compiled executables.
- **`jax.experimental.io_callback` fallback** (non-CPU backends, or a
  .so built without jaxlib headers, or TPUNET_FFI_COLLECTIVES=0):
  device buffers are staged to host, reduced, and staged back.

In-pod (ICI) collectives should keep using `jax.lax.psum` et al. — these
functions are the *between-hosts* tier of a hierarchical collective.

All ranks must execute the same dcn_* calls in the same order. The
io_callback path pins relative order with `ordered=True`. The FFI calls
are side-effecting custom calls ordered by the compiled schedule: ranks
compiling IDENTICAL programs schedule identically (the common case —
trainer, ZeRO, hierarchical psum), but a trace that bakes in the rank
(ring/zigzag attention's offsets) may schedule DATA-INDEPENDENT
collectives differently per rank, silently cross-matching them. The
contract: consecutive dcn_* calls in one trace must be related by data
flow — pack independent tensors into one collective (see
dcn_ring_attention's packed k/v exchange) or pass the earlier result via
the `after=` kwarg, which makes it an extra OPERAND of the later custom
call (a dependency no pass can dissolve; stablehlo.optimization_barrier
is NOT sufficient — XLA expands it away and measurably reordered such
collectives). `dcn_all_reduce(sum)` is differentiable: the VJP of a sum
all-reduce is a sum all-reduce of the cotangent.

Ticket API ordering: `dcn_all_reduce_start`/`dcn_all_reduce_finish` run on
the totally-ordered io_callback path ON PURPOSE (the native ticket pairing
contract is submission order across ranks, so the submission point must be
pinned, which `ordered=True` does and the FFI schedule does not). The flip
side: do NOT interleave start/finish with FFI `dcn_*` calls inside one
trace when that trace bakes in the rank (rank-asymmetric programs, e.g.
ring/zigzag attention offsets) WITHOUT bridging them by data flow. The two
mechanisms order through different machineries — io_callback through its
token chain, FFI through the compiled schedule — so XLA is free to
schedule an FFI collective BEFORE the callback-issued submission on one
rank and AFTER it on another, desyncing the ticket sequence exactly like
the unrelated-collectives hazard above. The bridge is `after=`, threaded
through BOTH directions: `dcn_all_reduce_start(x, after=(ffi_result,))` /
`dcn_all_reduce_finish(t, like, after=...)` make the callback an extra
CONSUMER of the earlier FFI results (operands of its io_callback, so the
token chain can't issue the submission until the FFI values exist), and an
FFI call's `after=` accepts the start's ticket or the finish's result to
pin the other direction (the ticket IS an array, hence a legal operand).
In rank-asymmetric traces either bridge every adjacency that way or keep
the ticket API on its own program segments.
"""

from __future__ import annotations

import ctypes
import resource
from functools import cache, partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import io_callback

from tpunet import distributed, telemetry
from tpunet.collectives import _c_contig


def _comm():
    return distributed.global_communicator()


_ffi_state = {"registered": False, "available": None}

# target name -> handler symbol in libtpunet.so (built all-or-none by the
# Makefile's jaxlib-header guard, so probing one symbol decides for all).
_FFI_TARGETS = {
    "tpunet_all_reduce": "TpunetFfiAllReduce",
    "tpunet_all_gather": "TpunetFfiAllGather",
    "tpunet_reduce_scatter": "TpunetFfiReduceScatter",
    "tpunet_broadcast": "TpunetFfiBroadcast",
    "tpunet_all_to_all": "TpunetFfiAllToAll",
    "tpunet_neighbor_exchange": "TpunetFfiNeighborExchange",
}


def _ffi_available() -> bool:
    """True when the zero-copy XLA custom-call path can serve this trace:
    CPU backend, handler symbols present in libtpunet.so (omitted when the
    .so was built without jaxlib headers), not disabled by
    TPUNET_FFI_COLLECTIVES=0. Decided at trace time; registration is
    one-shot per process."""
    import os

    if os.environ.get("TPUNET_FFI_COLLECTIVES", "1") != "1":
        return False
    if jax.default_backend() != "cpu":
        return False
    if _ffi_state["available"] is None:
        from tpunet import _native

        lib = _native.load()
        # ALL symbols must be present — a stale .so built when only
        # all_reduce existed must fall back to io_callback gracefully,
        # not crash at registration.
        _ffi_state["available"] = all(
            hasattr(lib, sym) for sym in _FFI_TARGETS.values())
    if not _ffi_state["available"]:
        return False
    if not _ffi_state["registered"]:
        from tpunet import _native

        lib = _native.load()
        for target, symbol in _FFI_TARGETS.items():
            jax.ffi.register_ffi_target(
                target, jax.ffi.pycapsule(getattr(lib, symbol)),
                platform="cpu")
        _ffi_state["registered"] = True
    return True


def _ffi_call(target: str, spec, x, after=(), **attrs):
    """Issue one FFI collective. `after` values become extra operands of
    the custom call (the handlers ignore them): a dependency no XLA pass
    can dissolve, pinning this collective AFTER the ones that produced
    them. (stablehlo.optimization_barrier is NOT enough — the pipeline
    expands it away and did reorder data-independent collectives in
    rank-asymmetric traces.)"""
    return jax.ffi.ffi_call(target, spec, has_side_effect=True)(
        x, *after, **attrs)


def _callback_result_spec(x: jax.Array | jnp.ndarray):
    return jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x))


def _host_operand(_comm, x):
    return _c_contig(np.asarray(x))


def _bridge(kind: str, collective, *, stage_in=_host_operand, stage_out=None):
    """The host callback of one io_callback collective: the program's whole
    share of the bridge, counted and cut into spans where the time can go
    (docs/DESIGN.md 6c; `kind` is a tpunet_bridge_*_total label).

    stage_in(comm, operand): what the program does before the native call,
        by default the operand as a C-contiguous ndarray;
    collective(comm, staged): the native call (its result buffer is a lazy
        np.empty inside Communicator, whose pages the native write faults
        in), for `_start` the submission, for `_finish` the wait;
    stage_out(comm, result): what is left between the native call's return
        and the callback's.
    Operands after the first are `after=` dependencies and are ignored."""

    def cb(x, *_deps):
        nbytes = int(x.nbytes)
        with telemetry.span("dcn.bridge", kind=kind, nbytes=nbytes):
            telemetry.bridge_call(kind, nbytes)
            return _stages(x, stage_in, collective, stage_out)

    return cb


def _stages(x, stage_in, collective, stage_out, **attrs):
    """One operand through the bridge's three child spans (`attrs`: further
    span attributes, the boundary exchange's `chunk`)."""
    nbytes = int(x.nbytes)
    with telemetry.span("dcn.bridge.stage_in", nbytes=nbytes, **attrs):
        comm = _comm()
        staged = stage_in(comm, x)
    with telemetry.span("dcn.bridge.collective", nbytes=nbytes, **attrs):
        out = collective(comm, staged)
    with telemetry.span("dcn.bridge.stage_out", **attrs):
        return out if stage_out is None else stage_out(comm, out)


# -- all-reduce -------------------------------------------------------------


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def _dcn_all_reduce_diff(x, op: str = "sum"):
    return _dcn_all_reduce_impl(x, op)


def dcn_all_reduce(x, op: str = "sum", *, after=()):
    """AllReduce `x` across all processes over the DCN transport.

    `after`: results of earlier data-independent dcn_* calls this one must
    follow (module docstring). The after-pinned form is NOT differentiable
    — training all-reduces are ordered by gradient data flow already; the
    kwarg exists for inference/serving traces."""
    if after:
        return _dcn_all_reduce_impl(x, op, tuple(after))
    return _dcn_all_reduce_diff(x, op)


def _dcn_all_reduce_impl(x, op: str, after=()):
    if _ffi_available():
        from tpunet.collectives import _OPS, _dtype_code

        return _ffi_call(
            "tpunet_all_reduce", _callback_result_spec(x), x, after,
            dtype=np.int64(_dtype_code(np.dtype(jnp.result_type(x)))),
            op=np.int64(_OPS[op]))
    cb = _bridge("all_reduce", lambda c, a: c.all_reduce(a, op))
    return io_callback(cb, _callback_result_spec(x), x, ordered=True)


def _dcn_all_reduce_fwd(x, op: str):
    if op != "sum":
        raise NotImplementedError(f"gradient of dcn_all_reduce only defined for sum, got {op}")
    return _dcn_all_reduce_impl(x, op), None


def _dcn_all_reduce_bwd(op: str, _res, g):
    return (_dcn_all_reduce_impl(g, "sum"),)


_dcn_all_reduce_diff.defvjp(_dcn_all_reduce_fwd, _dcn_all_reduce_bwd)


def dcn_psum(x):
    """`jax.lax.psum` shape, but across processes over DCN."""
    return dcn_all_reduce(x, "sum")


def dcn_pmean(x):
    w = distributed.world_size()
    return dcn_all_reduce(x, "sum") / jnp.asarray(w, dtype=jnp.result_type(x))


# -- all-reduce at a program boundary ----------------------------------------


def host_buffer_like(x) -> np.ndarray:
    """An uninitialised host array of x's shape and dtype, 64-byte aligned:
    the CPU backend's jax.device_put takes such an array as it is, without
    a copy (an np.empty of this size sits 16 bytes past a page and is
    copied). A result buffer for host_all_reduce's `out`."""
    nbytes = int(np.prod(x.shape)) * np.dtype(x.dtype).itemsize
    raw = np.empty(nbytes + 64, np.uint8)
    start = -raw.ctypes.data % 64
    return raw[start:start + nbytes].view(x.dtype).reshape(x.shape)


# The largest request glibc's malloc may serve from its heap, whatever the
# threshold is set to: a request that reaches it is ALWAYS a fresh mmap,
# returned by munmap at free, so every page of it is faulted in anew
# (glibc malloc/malloc.c: `# define DEFAULT_MMAP_THRESHOLD_MAX (4 * 1024 *
# 1024 * sizeof(long))`, 32 MiB on a 64-bit host).
_MMAP_CEILING = 32 << 20
# Room under the ceiling for what an allocation asks for beyond its block's
# bytes: malloc's 16-byte header, and the runtime's 64-byte alignment, which
# aligned_alloc serves from a request of alignment + MINSIZE more. Far more
# than they need; it is the distance the sweep below ran at.
_ALLOC_MARGIN = 64 << 10

# The boundary exchange's two numbers: bytes of one chunk, under the
# allocator's ceiling (a chunk's host block, the landing of its way out on an
# accelerator's rank and the grad program's output on a CPU rank, then comes
# from the heap the process keeps: retain_freed_host_blocks), and how many
# chunks' copies to the host run ahead of the chunk the exchange waits for.
# The winner of the sweep on four chips of the v5e host (world 4 over shared
# memory, the cell vgg16-dp4-shm's exchange; PERF.md section 6, PR 31, has
# every row, the sizes and depths that lost among them): the largest size
# tried under the ceiling, 17 ring calls for VGG16's 553 MB as when a chunk
# was 32 MiB; 31, 28, 24 and 16 MiB and depths 2 and 8 lose by 0.4 to 10%,
# the nearest of them inside the noise.
_CHUNK_BYTES = _MMAP_CEILING - _ALLOC_MARGIN
_COPIES_AHEAD = 4

_M_TRIM_THRESHOLD, _M_TOP_PAD, _M_MMAP_THRESHOLD = -1, -2, -3  # <malloc.h>


@cache
def retain_freed_host_blocks() -> bool:
    """Tell the C library's allocator, once a process, to serve every block
    under _MMAP_CEILING from its heap and to keep the heap when blocks are
    freed: the host blocks a boundary exchange allocates anew every step
    then lie in pages the process already owns, where a fresh mapping costs
    a page fault every 4 KiB of every block of every step. Three calls:
    mallopt(M_MMAP_THRESHOLD, ceiling), which also ends the allocator's
    dynamic threshold; mallopt(M_TRIM_THRESHOLD, INT_MAX), without which a
    heap's top is given back whenever the step's blocks have all been
    freed; and mallopt(M_TOP_PAD, 2 * ceiling), for the blocks a thread of
    the runtime allocates (a CPU rank's grad program): they come from that
    thread's arena, whose heaps are mappings of twice the ceiling that are
    unmapped whole when they empty, unless the heap before has less room
    left than the pad. The price: the process keeps its high-water heap
    (one vector's bytes; an arena each its own) instead of returning it
    between steps, for every block under the ceiling whoever allocates
    it, and an arena's new heap is mapped writable whole. mallopt takes
    an int: a free top of 2 GiB or more is still trimmed, so a vector
    that large is kept in part only. False, and nothing done, where the
    C library has no mallopt.

    Called when a _BoundaryStep is built: not at import, and not by a
    process whose steps never cross hosts."""
    try:
        mallopt = ctypes.CDLL(None).mallopt
    except (OSError, AttributeError):
        return False
    mallopt.argtypes, mallopt.restype = [ctypes.c_int, ctypes.c_int], ctypes.c_int
    # a list: all three are called whatever the first returns
    return all([mallopt(_M_MMAP_THRESHOLD, _MMAP_CEILING),
                mallopt(_M_TRIM_THRESHOLD, 2 ** 31 - 1),
                mallopt(_M_TOP_PAD, 2 * _MMAP_CEILING)])


def _minor_faults() -> int:
    """The process's minor page faults so far, every thread's."""
    return resource.getrusage(resource.RUSAGE_SELF).ru_minflt


def boundary_chunks(size: int, itemsize: int, world: int) -> tuple[int, ...]:
    """Element counts of the contiguous chunks in which a flat vector of
    `size` elements crosses the program boundary: whole chunks of the
    sweep's bytes, rounded to a count every rank of `world` gets an equal,
    64-byte-aligned share of, and what is left as the last one. A vector
    under one chunk is one chunk. The grad program cuts its output by this,
    so the counts are static shapes of both programs."""
    step = 64 * world // itemsize
    per = max(_CHUNK_BYTES // itemsize // step, 1) * step
    whole, rest = divmod(size, per)
    return (per,) * whole + ((rest,) if rest or not whole else ())


def reduced_like(chunks, shardings) -> tuple:
    """What host_all_reduce hands back for a sequence of chunks placed by
    `shardings`, as ShapeDtypeStructs: the reduced chunks, each where its
    chunk was; or, where jax.device_put is an alias of the host's buffer
    (every device is the CPU backend's: there is no way back to overlap
    with anything, and joining K operands costs a CPU program a pass over
    the vector), the whole reduced vector as ONE array."""
    if len(chunks) > 1 and all(d.platform == "cpu" for s in shardings
                               for d in s.device_set):
        return (jax.ShapeDtypeStruct((sum(int(c.size) for c in chunks),),
                                     chunks[0].dtype, sharding=shardings[0]),)
    return tuple(jax.ShapeDtypeStruct(c.shape, c.dtype, sharding=s)
                 for c, s in zip(chunks, shardings))


def host_all_reduce(chunks, out: np.ndarray, op: str = "sum") -> tuple:
    """AllReduce a flat device vector across processes BETWEEN two device
    programs, from the calling thread. `chunks` is the sequence of
    contiguous chunks a program cut the vector into (boundary_chunks; one
    chunk for a small vector); the result is the tuple reduced_like
    describes. For a collective that has a program boundary to stand at
    (the trainer's gradient exchange, between backward and the optimizer):
    no host-transfer operation in either program, and both stay cacheable.

    Three stages a chunk, and the chunks in different stages at one time:
      out   chunk k's copy to the host and those of the _COPIES_AHEAD chunks
            after it are running (copy_to_host_async: the runtime's own
            array transfer, behind the program that produces the chunk)
            when np.asarray waits for chunk k alone (a view of a CPU
            array); a bounded number, because the runtime serves the
            copies it is given side by side, and all K at once land
            together, late;
      ring  one Communicator.all_reduce of chunk k into its slice of `out`,
            on this thread, while the chunks after k are still landing;
      back  jax.device_put of that slice to where the chunk was, which
            returns before an accelerator has the bytes: chunk k goes back
            while chunk k+1 is in the ring. (On the CPU backend, where the
            put is an alias: one put of the whole buffer after the last
            ring.)
    So the exchange tends to its longest stage plus one chunk's way through
    the other two, not the three stages' sum. One `dcn.bridge` span and one
    count of tpunet_bridge_{calls,bytes}_total an exchange, as for the
    in-jit bridge; its three child spans once a chunk, with `chunk`;
    tpunet_bridge_chunks_total and ..._in_flight_max say how many chunks
    crossed and how many were between the start of their copy out and the
    return of their device_put at one time;
    tpunet_bridge_minor_faults_total the page faults the whole process took
    across the span (over the bytes: near 256 a MiB when every chunk's host
    block is a fresh mapping, near 0 when retain_freed_host_blocks
    engaged).

    `out` (from host_buffer_like, flat, of the whole vector): the ring's
    result buffer, kept by the caller across calls. jax.device_put returns
    before an accelerator has the bytes, and on the CPU backend the array
    that comes back IS `out`: so the caller may pass `out` again only once
    whatever consumed the last result has finished."""
    chunks = tuple(chunks)
    nbytes = sum(int(c.nbytes) for c in chunks)
    pieces = np.split(out, np.cumsum([int(c.size) for c in chunks])[:-1])
    like = reduced_like(chunks, [c.sharding for c in chunks])
    back, deepest = [], 0
    with telemetry.span("dcn.bridge", kind="all_reduce", nbytes=nbytes):
        telemetry.bridge_call("all_reduce", nbytes)
        faults = _minor_faults()
        started = 0
        for k, (c, piece) in enumerate(zip(chunks, pieces)):
            # chunk k and the _COPIES_AHEAD after it are on their way out
            # while chunk k is waited for and reduced
            while started < min(k + 1 + _COPIES_AHEAD, len(chunks)):
                chunks[started].copy_to_host_async()
                started += 1
            deepest = max(deepest, started - k)

            # _stages calls both before it returns: they see this k
            def ring(comm, staged):
                return comm.all_reduce(staged, op, out=piece)

            def put(comm, reduced):
                if len(like) == len(chunks):
                    back.append(jax.device_put(reduced, c.sharding))
                elif k == len(chunks) - 1:  # an alias: the vector whole
                    back.append(jax.device_put(out, like[0].sharding))

            _stages(c, _host_operand, ring, put, chunk=k)
        telemetry.bridge_chunks("all_reduce", len(chunks), deepest)
        telemetry.bridge_minor_faults("all_reduce", _minor_faults() - faults)
    return tuple(back)


# -- nonblocking all-reduce (gradient-bucket overlap) -----------------------

# Outstanding AsyncResults keyed by (communicator identity, native ticket).
# Native tickets are sequential per communicator, so two live Communicators
# both count from 1 — a ticket-only key would silently pair a finish with
# the wrong communicator's buffer. id(comm) is stable while any of its
# results are pending (each AsyncResult holds a strong comm ref). The start
# callback pins the buffers here; the finish callback releases them.
# max_in_flight is the observable proof that buckets actually overlapped
# (tests assert on it).
_async_pending: dict[tuple[int, int], Any] = {}
_async_stats = {"in_flight": 0, "max_in_flight": 0}


def _register_pending(comm, res) -> int:
    """Pin `res` until its finish callback; returns the uint32 wire ticket.
    uint32 keeps the ticket jax-representable without x64; native tickets
    are sequential from 1 so wraparound is out of reach."""
    ticket = res._ticket & 0xFFFFFFFF
    _async_pending[(id(comm), ticket)] = res
    _async_stats["in_flight"] += 1
    _async_stats["max_in_flight"] = max(
        _async_stats["max_in_flight"], _async_stats["in_flight"]
    )
    return ticket


def _pop_pending(comm, ticket: int):
    try:
        res = _async_pending.pop((id(comm), ticket))
    except KeyError:
        raise RuntimeError(
            f"no pending async collective with ticket {ticket} on the current "
            "global communicator — dcn_all_reduce_finish without a matching "
            "start, or the communicator was re-initialized mid-flight"
        ) from None
    _async_stats["in_flight"] -= 1
    return res


def _drop_pending_for(comm) -> int:
    """Forget every pending async op of `comm` (called by
    distributed.finalize before closing it): the entries would otherwise be
    unreachable — _pop_pending keys on the CURRENT global comm — pinning
    their buffers and inflating in_flight for the process lifetime."""
    stale = [k for k in _async_pending if k[0] == id(comm)]
    for k in stale:
        del _async_pending[k]
        _async_stats["in_flight"] -= 1
    return len(stale)


def dcn_async_stats() -> dict[str, int]:
    """Snapshot of nonblocking-collective depth (host-side, for tests/bench)."""
    return dict(_async_stats)


def dcn_async_stats_reset() -> None:
    _async_stats["in_flight"] = 0
    _async_stats["max_in_flight"] = 0


def dcn_all_reduce_start(x, op: str = "sum", *, after=()):
    """Begin a nonblocking AllReduce of `x`; returns a ticket (uint32
    scalar) to pass to `dcn_all_reduce_finish`. The reduction runs on the
    native worker thread, overlapping whatever compute XLA schedules
    between the start and finish callbacks — the bucketed-gradient-overlap
    primitive.

    Stays on the totally-ordered io_callback path even when the FFI
    collectives are enabled: cross-rank ticket pairing is SUBMISSION order,
    which `ordered=True` pins and the FFI schedule does not. `after=`:
    results of earlier data-independent FFI `dcn_*` calls this submission
    must follow — they become extra operands of the start callback, so the
    io_callback token chain cannot issue the submission before the FFI
    collectives produced them (the cross-machinery ordering bridge; module
    docstring "Ticket API ordering"). The returned ticket is itself a
    legal `after=` operand for a later FFI call, pinning the reverse
    direction."""

    cb = _bridge("all_reduce_start", lambda c, a: c.iall_reduce(a, op),
                 stage_out=lambda c, res: np.uint32(_register_pending(c, res)))
    return io_callback(cb, jax.ShapeDtypeStruct((), jnp.uint32), x,
                       *tuple(after), ordered=True)


def dcn_all_reduce_finish(ticket, like, *, after=()):
    """Complete the nonblocking AllReduce for `ticket`; returns the reduced
    array (shape/dtype of `like`, the array passed to the start call).
    `after=` pins this completion behind earlier FFI `dcn_*` results, same
    contract as `dcn_all_reduce_start`."""

    cb = _bridge("all_reduce_finish", lambda c, res: res.wait(),
                 stage_in=lambda c, t: _pop_pending(c, int(t)))
    return io_callback(cb, _callback_result_spec(like), ticket,
                       *tuple(after), ordered=True)


# -- other collectives ------------------------------------------------------


def dcn_all_gather(x, *, after=()):
    """Gather `x` from every process: result shape (world, *x.shape).
    `after`: results of earlier data-independent dcn_* calls this one must
    follow (module docstring; ignored on the io_callback path, which is
    totally ordered)."""
    w = distributed.world_size()
    spec = jax.ShapeDtypeStruct((w,) + tuple(jnp.shape(x)), jnp.result_type(x))
    if _ffi_available():
        return _ffi_call("tpunet_all_gather", spec, x, after)

    cb = _bridge("all_gather", lambda c, a: c.all_gather(a))
    return io_callback(cb, spec, x, ordered=True)


def dcn_reduce_scatter(x, op: str = "sum", *, after=()):
    """x: leading axis divisible by world; returns this process's reduced
    shard (shape[0]/world leading axis)."""
    w = distributed.world_size()
    shape = tuple(jnp.shape(x))
    if shape[0] % w != 0:
        raise ValueError(f"leading axis {shape[0]} not divisible by world size {w}")

    spec = jax.ShapeDtypeStruct((shape[0] // w,) + shape[1:], jnp.result_type(x))
    if _ffi_available():
        from tpunet.collectives import _OPS, _dtype_code

        return _ffi_call(
            "tpunet_reduce_scatter", spec, x, after,
            dtype=np.int64(_dtype_code(np.dtype(jnp.result_type(x)))),
            op=np.int64(_OPS[op]))

    cb = _bridge("reduce_scatter", lambda c, a: c.reduce_scatter(a, op))
    return io_callback(cb, spec, x, ordered=True)


def dcn_all_to_all(x, *, after=()):
    """AllToAll across processes: x has leading axis == world, block j goes
    to process j; the result's block j came from process j. Shape-preserving.
    The cross-host leg of Ulysses sequence parallelism and MoE dispatch."""
    w = distributed.world_size()
    shape = tuple(jnp.shape(x))
    if not shape or shape[0] != w:
        raise ValueError(f"leading axis must equal world size {w}, got {shape}")

    if _ffi_available():
        return _ffi_call("tpunet_all_to_all", _callback_result_spec(x), x,
                         after)

    cb = _bridge("all_to_all", lambda c, a: c.all_to_all(a))
    return io_callback(cb, _callback_result_spec(x), x, ordered=True)


def dcn_broadcast(x, root: int = 0, *, after=()):
    if _ffi_available():
        return _ffi_call("tpunet_broadcast", _callback_result_spec(x), x,
                         after, root=np.int64(root))

    cb = _bridge("broadcast", lambda c, a: c.broadcast(a, root))
    return io_callback(cb, _callback_result_spec(x), x, ordered=True)


def dcn_neighbor_exchange(x, *, after=()):
    """Send x to (rank+1)%world, receive from (rank-1+world)%world — the
    ring-shift step of ring attention / sequence parallelism, across hosts.
    `after`: earlier collectives this exchange must follow (module
    docstring)."""
    if _ffi_available():
        return _ffi_call("tpunet_neighbor_exchange",
                         _callback_result_spec(x), x, after)

    cb = _bridge("neighbor_exchange", lambda c, a: c.neighbor_exchange(a))
    return io_callback(cb, _callback_result_spec(x), x, ordered=True)


def dcn_barrier():
    """Host-level barrier (outside jit)."""
    _comm().barrier()


# -- hierarchical helper ----------------------------------------------------


def hierarchical_psum(x, axis_name: str | None = None):
    """Two-tier psum: `lax.psum` over the in-pod mesh axis (ICI, XLA
    collectives), then a DCN all-reduce across processes. This is the shape
    a v5e-32 (4 hosts x 8 chips) gradient sync takes: ICI does the heavy
    intra-pod reduction at interconnect speed, DCN carries the
    already-reduced copy. Under `shard_map` the DCN all-reduce runs once on
    EVERY device of the process, each with the same reduced block (four
    all-reduces a call on a four-chip host, chip_smoke.py --four-chips): one
    copy per host is what the layout wants and not yet what it does.

    Requires `tpunet.distributed.initialize()` BEFORE the first trace: the
    world-size decision is baked into the jitted executable, so a lazy
    "skip DCN when uninitialized" fallback would silently cache an unsynced
    gradient step if tracing ever preceded initialization.
    """
    if axis_name is not None:
        x = jax.lax.psum(x, axis_name)
    if distributed.world_size() > 1:  # raises if initialize() was not called
        x = dcn_all_reduce(x, "sum")
    return x
