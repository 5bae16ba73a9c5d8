"""Python collectives over the tpunet ring communicator.

The role NCCL's algorithm layer played above the reference plugin, exposed
to Python/NumPy. All ranks must call the same collectives in the same order
(MPI semantics). Arrays must be C-contiguous; results come back as NumPy
arrays of the input dtype.

Supported dtypes: float32, float64, bfloat16 (via ml_dtypes), int32, int64,
uint8. Ops: sum, prod, min, max.
"""

from __future__ import annotations

import ctypes
import os
from typing import Any

import numpy as np

from tpunet import _native

try:  # bf16 is first-class on TPU; ml_dtypes ships with jax
    import ml_dtypes

    _BF16 = np.dtype(ml_dtypes.bfloat16)
except ImportError:  # pragma: no cover
    _BF16 = None

_OPS = {"sum": 0, "prod": 1, "min": 2, "max": 3}


def _dtype_code(dt: np.dtype) -> int:
    dt = np.dtype(dt)
    if dt == np.float32:
        return 0
    if dt == np.float64:
        return 1
    if _BF16 is not None and dt == _BF16:
        return 2
    if dt == np.int32:
        return 3
    if dt == np.int64:
        return 4
    if dt == np.uint8:
        return 5
    raise TypeError(f"unsupported dtype for tpunet collectives: {dt}")


def _c_contig(arr: np.ndarray) -> np.ndarray:
    return arr if arr.flags.c_contiguous else np.ascontiguousarray(arr)


class AsyncResult:
    """Handle for a nonblocking collective. Pins the send/recv buffers until
    `wait()` — the native layer reads/writes them from its worker thread."""

    def __init__(self, comm: "Communicator", ticket: int, send: np.ndarray,
                 out: np.ndarray):
        self._comm = comm
        self._ticket = ticket
        self._send = send  # keep alive until wait
        self._out: np.ndarray | None = out

    def test(self) -> bool:
        """True iff the collective has completed (non-blocking)."""
        if self._send is None:  # already waited: the native ticket is gone
            return True
        done = ctypes.c_uint8(0)
        _native.check(
            self._comm._lib.tpunet_comm_ticket_test(
                self._comm._id, self._ticket, ctypes.byref(done)
            ),
            "ticket_test",
        )
        return bool(done.value)

    def wait(self) -> np.ndarray:
        """Block until complete; returns the result array. Idempotent."""
        if self._send is not None:
            try:
                _native.check(
                    self._comm._lib.tpunet_comm_ticket_wait(self._comm._id, self._ticket),
                    "ticket_wait",
                )
            finally:
                # Error or not, a returned WaitTicket means the native job
                # reached completion (or was dropped unstarted) — the worker
                # thread no longer touches the buffers, so release the pins.
                self._send = None
        return self._out

    def __del__(self):
        # Dropping an un-waited result must NOT free the buffers while the
        # native worker thread may still be reducing into them (observed:
        # exit-time SIGSEGV when a peer died with queued tickets). Quiesce
        # first; after a comm error the remaining jobs fail fast, so this
        # wait is bounded. Raw call, no check: errors here are expected
        # (failed jobs, already-destroyed comm) and __del__ must not raise.
        send = getattr(self, "_send", None)
        if send is not None:
            try:
                self._comm._lib.tpunet_comm_ticket_wait(self._comm._id, self._ticket)
            except Exception:  # noqa: BLE001 — interpreter teardown
                pass
            self._send = None


class Communicator:
    """Ring communicator; rank/world/coordinator default from env
    (TPUNET_RANK/RANK, TPUNET_WORLD_SIZE/WORLD_SIZE, TPUNET_COORDINATOR).

    Failure model (docs/DESIGN.md): collectives raise typed subclasses of
    ``_native.NativeError`` — ``CorruptionError`` for a CRC32C-detected wire
    corruption (TPUNET_CRC=1; the comm survives), ``ProgressTimeoutError``
    when the progress watchdog (TPUNET_PROGRESS_TIMEOUT_MS) flags a
    live-but-stuck peer, and plain NativeError for disconnect/poison. A
    single data-stream loss is NOT an error: the transport fails over onto
    the surviving streams and the collective completes (see
    ``tpunet_stream_failovers_total`` in telemetry.metrics())."""

    def __init__(
        self,
        coordinator: str | None = None,
        rank: int | None = None,
        world_size: int | None = None,
        wire_dtype: str | None = None,
        algo: str | None = None,
        traffic_class: str | None = None,
    ):
        env = os.environ
        coordinator = coordinator or env.get("TPUNET_COORDINATOR", "127.0.0.1:29500")
        rank = rank if rank is not None else int(env.get("TPUNET_RANK", env.get("RANK", "0")))
        world_size = (
            world_size
            if world_size is not None
            else int(env.get("TPUNET_WORLD_SIZE", env.get("WORLD_SIZE", "1")))
        )
        self._lib = _native.load()
        cid = ctypes.c_size_t(0)
        # wire_dtype selects the f32 wire compression codec ("f32"/"bf16"/
        # "int8"; None defers to TPUNET_WIRE_DTYPE, default f32). algo pins
        # the collective schedule ("auto"/"ring"/"rhd"/"tree"; None defers
        # to TPUNET_ALGO, default auto — per-(collective, size, world)
        # selection through the built-in thresholds or the
        # TPUNET_DISPATCH_TABLE JSON from `busbw_sweep --emit-dispatch`).
        # traffic_class pins the QoS lane every comm this communicator
        # wires will carry ("latency"/"bulk"/"control"; None defers to
        # TPUNET_TRAFFIC_CLASS, default bulk — gradient comms unchanged).
        # All three are negotiated at wiring time: a cross-rank
        # disagreement raises CodecMismatchError (codec) / NativeError
        # (algo, dispatch table, traffic class) on every rank before any
        # payload could be mis-decoded, any half-world schedule could
        # deadlock, or half a group could ride another QoS lane.
        _native.check(
            self._lib.tpunet_comm_create_ex(
                coordinator.encode(), rank, world_size,
                (wire_dtype or "").encode(), (algo or "").encode(),
                (traffic_class or "").encode(),
                ctypes.byref(cid),
            ),
            "comm_create",
        )
        self._id = cid.value
        self.rank = rank
        self.world_size = world_size
        codec = ctypes.c_int32(0)
        _native.check(
            self._lib.tpunet_comm_wire_dtype(self._id, ctypes.byref(codec)),
            "comm_wire_dtype",
        )
        #: Negotiated wire codec name — authoritative (read back from the
        #: native layer, so env-default and explicit construction agree).
        self.wire_dtype: str = {0: "f32", 1: "bf16", 2: "int8"}[codec.value]

    # -- collectives -------------------------------------------------------

    def all_reduce(self, arr: Any, op: str = "sum", inplace: bool = False,
                   out: np.ndarray | None = None) -> np.ndarray:
        """AllReduce. inplace=True reduces into `arr` itself (must be a
        C-contiguous ndarray) — skips the send→recv staging copy, which
        matters at 100MB+ gradient-bucket sizes. `out`: a writable
        C-contiguous ndarray of arr's shape and dtype to reduce into and
        return, for a caller that keeps its result buffer across calls (a
        new np.empty of 553 MB costs its first-touch page faults on every
        call: 0.6 s on the v5e's host, PERF.md section 7)."""
        caller_arr = arr
        arr = np.asarray(arr)
        if inplace and (arr is not caller_arr or not arr.flags.c_contiguous):
            raise ValueError(
                "inplace=True requires a C-contiguous ndarray (a staging "
                "copy would leave the caller's buffer unchanged)"
            )
        arr = _c_contig(arr)
        if inplace:
            if out is not None:
                raise ValueError("inplace=True reduces into arr: pass no out")
            out = arr
        elif out is None:
            out = np.empty_like(arr)
        elif (not isinstance(out, np.ndarray) or out.shape != arr.shape
              or out.dtype != arr.dtype or not out.flags.c_contiguous
              or not out.flags.writeable):
            raise ValueError(
                f"out must be a writable C-contiguous ndarray of shape "
                f"{arr.shape} and dtype {arr.dtype}")
        _native.check(
            self._lib.tpunet_comm_all_reduce(
                self._id,
                arr.ctypes.data if arr.size else None,
                out.ctypes.data if out.size else None,
                arr.size,
                _dtype_code(arr.dtype),
                _OPS[op],
            ),
            "all_reduce",
        )
        return out

    def iall_reduce(self, arr: Any, op: str = "sum") -> AsyncResult:
        """Nonblocking AllReduce: returns immediately with an AsyncResult;
        the reduction runs on the communicator's worker thread (submission
        order across ranks must match). `result.wait()` yields the reduced
        array — this is how a trainer overlaps gradient-bucket sync with
        backward compute."""
        arr = _c_contig(np.asarray(arr))
        out = np.empty_like(arr)
        ticket = ctypes.c_uint64(0)
        _native.check(
            self._lib.tpunet_comm_iall_reduce(
                self._id,
                arr.ctypes.data if arr.size else None,
                out.ctypes.data if out.size else None,
                arr.size,
                _dtype_code(arr.dtype),
                _OPS[op],
                ctypes.byref(ticket),
            ),
            "iall_reduce",
        )
        return AsyncResult(self, ticket.value, arr, out)

    def reduce_scatter(self, arr: Any, op: str = "sum") -> np.ndarray:
        """arr: leading axis divisible by world_size; returns this rank's
        reduced shard (shape[0] / world_size leading axis)."""
        arr = _c_contig(np.asarray(arr))
        if arr.shape[0] % self.world_size != 0:
            raise ValueError(
                f"leading axis {arr.shape[0]} not divisible by world size {self.world_size}"
            )
        out_shape = (arr.shape[0] // self.world_size,) + arr.shape[1:]
        out = np.empty(out_shape, dtype=arr.dtype)
        _native.check(
            self._lib.tpunet_comm_reduce_scatter(
                self._id,
                arr.ctypes.data if arr.size else None,
                out.ctypes.data if out.size else None,
                out.size,
                _dtype_code(arr.dtype),
                _OPS[op],
            ),
            "reduce_scatter",
        )
        return out

    def all_gather(self, arr: Any) -> np.ndarray:
        """Returns shape (world_size, *arr.shape), rank-ordered."""
        arr = _c_contig(np.asarray(arr))
        out = np.empty((self.world_size,) + arr.shape, dtype=arr.dtype)
        _native.check(
            self._lib.tpunet_comm_all_gather(
                self._id,
                arr.ctypes.data if arr.size else None,
                out.ctypes.data if out.size else None,
                arr.nbytes,
            ),
            "all_gather",
        )
        return out

    def broadcast(self, arr: Any, root: int = 0) -> np.ndarray:
        arr = np.ascontiguousarray(np.asarray(arr)).copy()
        _native.check(
            self._lib.tpunet_comm_broadcast(
                self._id, arr.ctypes.data if arr.size else None, arr.nbytes, root
            ),
            "broadcast",
        )
        return arr

    def all_to_all(self, arr: Any) -> np.ndarray:
        """arr: leading axis == world_size, block j destined for rank j.
        Returns the same shape with block j originating at rank j — the
        Ulysses sequence-parallel / cross-host MoE dispatch primitive."""
        arr = _c_contig(np.asarray(arr))
        if arr.shape[0] != self.world_size:
            raise ValueError(
                f"leading axis {arr.shape[0]} must equal world size {self.world_size}"
            )
        out = np.empty_like(arr)
        _native.check(
            self._lib.tpunet_comm_all_to_all(
                self._id,
                arr.ctypes.data if arr.size else None,
                out.ctypes.data if out.size else None,
                arr.nbytes // self.world_size,
            ),
            "all_to_all",
        )
        return out

    def all_to_all_typed(self, arr: Any) -> np.ndarray:
        """Typed AllToAll: like :meth:`all_to_all`, but blocks are ELEMENTS
        of the array's dtype, and float32 blocks honor the communicator's
        negotiated wire codec (``wire_dtype="bf16"``/``"int8"``) — every
        non-self block is encoded once at the source (int8 scale blocks
        restart per (src, dst) block) and decoded once at the destination,
        so results are bit-identical across the pairwise / relay /
        hierarchical routes and each block's error stays inside the
        documented |err| <= amax/254 bound. The MoE dispatch/combine
        primitive (tpunet.workloads.moe)."""
        arr = _c_contig(np.asarray(arr))
        if arr.shape[0] != self.world_size:
            raise ValueError(
                f"leading axis {arr.shape[0]} must equal world size {self.world_size}"
            )
        out = np.empty_like(arr)
        _native.check(
            self._lib.tpunet_comm_all_to_all_typed(
                self._id,
                arr.ctypes.data if arr.size else None,
                out.ctypes.data if out.size else None,
                arr.size // self.world_size,
                _dtype_code(arr.dtype),
            ),
            "all_to_all_typed",
        )
        return out

    def iall_to_all(self, arr: Any) -> AsyncResult:
        """Nonblocking AllToAll (byte-oriented): returns immediately with an
        AsyncResult; mesh-routed schedules run on the communicator's
        dedicated mesh worker, so an async AllToAll overlaps async ring
        AllReduces on disjoint comms instead of queueing behind them.
        Submission order across ranks must match, like iall_reduce."""
        arr = _c_contig(np.asarray(arr))
        if arr.shape[0] != self.world_size:
            raise ValueError(
                f"leading axis {arr.shape[0]} must equal world size {self.world_size}"
            )
        out = np.empty_like(arr)
        ticket = ctypes.c_uint64(0)
        _native.check(
            self._lib.tpunet_comm_iall_to_all(
                self._id,
                arr.ctypes.data if arr.size else None,
                out.ctypes.data if out.size else None,
                arr.nbytes // self.world_size,
                ctypes.byref(ticket),
            ),
            "iall_to_all",
        )
        return AsyncResult(self, ticket.value, arr, out)

    def neighbor_exchange(self, arr: Any) -> np.ndarray:
        """Send arr to (rank+1)%W, receive the same-shaped message from
        (rank-1+W)%W — the ring-attention / sequence-parallel shift step."""
        arr = _c_contig(np.asarray(arr))
        out = np.empty_like(arr)
        got = ctypes.c_uint64(0)
        _native.check(
            self._lib.tpunet_comm_neighbor_exchange(
                self._id,
                arr.ctypes.data if arr.size else None,
                arr.nbytes,
                out.ctypes.data if out.size else None,
                out.nbytes,
                ctypes.byref(got),
            ),
            "neighbor_exchange",
        )
        if got.value != arr.nbytes:
            raise RuntimeError(
                f"neighbor_exchange size mismatch: sent {arr.nbytes}, got {got.value}"
            )
        return out

    def barrier(self) -> None:
        _native.check(self._lib.tpunet_comm_barrier(self._id), "barrier")

    def set_as_default(self) -> None:
        """Make this the process-default communicator — the handle the XLA
        FFI custom-call collectives (tpunet.interop) resolve at CALL time,
        so elastic recovery can swap the communicator under
        already-compiled executables (comm_destroy clears it)."""
        _native.check(
            self._lib.tpunet_comm_set_default(self._id), "comm_set_default")

    def close(self) -> None:
        if self._id:
            cid = ctypes.c_size_t(self._id)
            self._id = 0
            _native.check(self._lib.tpunet_comm_destroy(ctypes.byref(cid)), "comm_destroy")

    def __enter__(self) -> "Communicator":
        return self

    def __exit__(self, *exc: Any) -> None:
        self.close()
