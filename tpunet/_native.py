"""Loader + raw ctypes signatures for libtpunet.so (the C ABI, c_api.h).

Builds the native library on demand (``make -C cpp -j build/libtpunet.so``)
under a file lock so concurrent test processes don't race the build. The
reference shipped its native core the same way conceptually: cargo staticlib
+ make shared object (reference: cc/Makefile:9-16).
"""

from __future__ import annotations

import ctypes
import fcntl
import hashlib
import os
import subprocess
import sys
from pathlib import Path

_REPO_ROOT = Path(__file__).resolve().parent.parent
_CPP_DIR = _REPO_ROOT / "cpp"
_LIB_PATH = _CPP_DIR / "build" / "libtpunet.so"
# Digest of the sources the library was built from, written beside it.
_STAMP_PATH = _LIB_PATH.with_name("libtpunet.so.sources")

TPUNET_OK = 0
TPUNET_ERR_NULL = -1
TPUNET_ERR_INVALID = -2
TPUNET_ERR_INNER = -3
# Failure-model codes (docs/DESIGN.md "Failure model"):
TPUNET_ERR_CORRUPT = -4   # per-chunk CRC32C mismatch (TPUNET_CRC=1)
TPUNET_ERR_TIMEOUT = -5   # progress watchdog (TPUNET_PROGRESS_TIMEOUT_MS)
TPUNET_ERR_VERSION = -6   # wire-framing version mismatch with the peer
TPUNET_ERR_CODEC = -7     # ranks disagree on the collective wire codec
TPUNET_ERR_QOS_ADMISSION = -8  # QoS class in-flight budget full (retryable)
TPUNET_ERR_REWIRE = -9    # elastic rewire exceeded TPUNET_REWIRE_TIMEOUT_MS
TPUNET_ERR_WEIGHT_SWAP = -10  # live weight publication aborted (retryable)

HANDLE_SIZE = 64


class SocketHandle(ctypes.Structure):
    _fields_ = [("data", ctypes.c_uint8 * HANDLE_SIZE)]


class NetProperties(ctypes.Structure):
    _fields_ = [
        ("name", ctypes.c_char_p),
        ("pci_path", ctypes.c_char_p),
        ("guid", ctypes.c_uint64),
        ("ptr_support", ctypes.c_int32),
        ("speed_mbps", ctypes.c_int32),
        ("port", ctypes.c_int32),
        ("max_comms", ctypes.c_int32),
    ]


def _sources_digest() -> str:
    """SHA-256 over everything the library target compiles: the Makefile,
    cpp/src and the public headers. Content, not file times: a copied or
    freshly checked-out tree keeps its bytes but not its mtimes."""
    files = [_CPP_DIR / "Makefile"]
    for sub in ("src", "include/tpunet"):
        files += sorted(f for f in (_CPP_DIR / sub).rglob("*")
                        if f.suffix in (".cc", ".h"))
    h = hashlib.sha256()
    for f in files:
        h.update(f.relative_to(_CPP_DIR).as_posix().encode() + b"\0")
        h.update(f.read_bytes())
    return h.hexdigest()


def build_native(force: bool = False) -> Path:
    """Build cpp/build/libtpunet.so if it is missing or was built from other
    sources than the ones on disk; `force` rebuilds regardless. Only the
    library target, in parallel, every object recompiled (`make -B`): the
    objects a copied build directory holds cannot be trusted by file time
    either. Safe across processes."""
    lock_path = _CPP_DIR / ".build.lock"
    with open(lock_path, "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        try:
            digest = _sources_digest()
            fresh = (
                not force
                and _LIB_PATH.exists()
                and _STAMP_PATH.exists()
                and _STAMP_PATH.read_text().strip() == digest
            )
            if not fresh:
                _STAMP_PATH.unlink(missing_ok=True)
                subprocess.run(
                    ["make", "-C", str(_CPP_DIR), "-B",
                     f"-j{os.cpu_count() or 1}", f"PYTHON={sys.executable}",
                     "build/libtpunet.so"],
                    check=True,
                    capture_output=True,
                    text=True,
                )
                _STAMP_PATH.write_text(digest + "\n")
        except subprocess.CalledProcessError as e:  # surface compiler output
            raise RuntimeError(
                f"native build failed:\n{e.stdout}\n{e.stderr}"
            ) from e
        finally:
            fcntl.flock(lock, fcntl.LOCK_UN)
    return _LIB_PATH


_lib: ctypes.CDLL | None = None


def load(lib_file: Path | None = None) -> ctypes.CDLL:
    """Load (building if needed) and memoize the native library. An explicit
    `lib_file` on the first call wins over TPUNET_LIBRARY_PATH and a bundled
    copy — how chip_smoke.py pins the process to the file it just built."""
    global _lib
    if _lib is not None:
        if lib_file is not None and Path(_lib._name) != Path(lib_file):
            raise RuntimeError(
                f"libtpunet already loaded from {_lib._name}, not {lib_file}")
        return _lib
    if lib_file is None:
        path = os.environ.get("TPUNET_LIBRARY_PATH", "")
        bundled = Path(__file__).resolve().parent / "lib" / "libtpunet.so"
        if path:
            lib_file = Path(path)
        elif bundled.exists():  # installed wheel: .so shipped as package data
            lib_file = bundled
        else:  # source checkout: build on demand
            lib_file = build_native()
    lib = ctypes.CDLL(str(lib_file))

    u = ctypes.c_uintptr if hasattr(ctypes, "c_uintptr") else ctypes.c_size_t
    i32, u8, u64 = ctypes.c_int32, ctypes.c_uint8, ctypes.c_uint64
    P = ctypes.POINTER

    lib.tpunet_c_create.argtypes = [P(u)]
    lib.tpunet_c_create.restype = i32
    lib.tpunet_c_create_ex.argtypes = [ctypes.c_char_p, P(u)]
    lib.tpunet_c_create_ex.restype = i32
    lib.tpunet_c_destroy.argtypes = [P(u)]
    lib.tpunet_c_destroy.restype = i32
    lib.tpunet_c_devices.argtypes = [u, P(i32)]
    lib.tpunet_c_devices.restype = i32
    lib.tpunet_c_get_properties.argtypes = [u, i32, P(NetProperties)]
    lib.tpunet_c_get_properties.restype = i32
    lib.tpunet_c_listen.argtypes = [u, i32, P(SocketHandle), P(u)]
    lib.tpunet_c_listen.restype = i32
    lib.tpunet_c_connect.argtypes = [u, i32, P(SocketHandle), P(u)]
    lib.tpunet_c_connect.restype = i32
    lib.tpunet_c_accept.argtypes = [u, u, P(u)]
    lib.tpunet_c_accept.restype = i32
    lib.tpunet_c_isend.argtypes = [u, u, ctypes.c_void_p, u64, P(u)]
    lib.tpunet_c_isend.restype = i32
    lib.tpunet_c_irecv.argtypes = [u, u, ctypes.c_void_p, u64, P(u)]
    lib.tpunet_c_irecv.restype = i32
    lib.tpunet_c_test.argtypes = [u, u, P(u8), P(u64)]
    lib.tpunet_c_test.restype = i32
    lib.tpunet_c_wait.argtypes = [u, u, P(u64)]
    lib.tpunet_c_wait.restype = i32
    lib.tpunet_c_close_send.argtypes = [u, u]
    lib.tpunet_c_close_send.restype = i32
    lib.tpunet_c_close_recv.argtypes = [u, u]
    lib.tpunet_c_close_recv.restype = i32
    lib.tpunet_c_close_listen.argtypes = [u, u]
    lib.tpunet_c_close_listen.restype = i32
    lib.tpunet_c_last_error.argtypes = []
    lib.tpunet_c_last_error.restype = ctypes.c_char_p

    lib.tpunet_comm_create.argtypes = [ctypes.c_char_p, i32, i32, P(u)]
    lib.tpunet_comm_create.restype = i32
    lib.tpunet_comm_create_ex.argtypes = [
        ctypes.c_char_p, i32, i32, ctypes.c_char_p, ctypes.c_char_p,
        ctypes.c_char_p, P(u),
    ]
    lib.tpunet_comm_create_ex.restype = i32
    lib.tpunet_comm_wire_dtype.argtypes = [u, P(i32)]
    lib.tpunet_comm_wire_dtype.restype = i32
    lib.tpunet_comm_destroy.argtypes = [P(u)]
    lib.tpunet_comm_destroy.restype = i32
    lib.tpunet_comm_rank.argtypes = [u, P(i32), P(i32)]
    lib.tpunet_comm_rank.restype = i32
    lib.tpunet_comm_all_reduce.argtypes = [u, ctypes.c_void_p, ctypes.c_void_p, u64, i32, i32]
    lib.tpunet_comm_all_reduce.restype = i32
    lib.tpunet_comm_set_default.argtypes = [u]
    lib.tpunet_comm_set_default.restype = i32
    lib.tpunet_comm_get_default.argtypes = []
    lib.tpunet_comm_get_default.restype = u
    lib.tpunet_comm_reduce_scatter.argtypes = [u, ctypes.c_void_p, ctypes.c_void_p, u64, i32, i32]
    lib.tpunet_comm_reduce_scatter.restype = i32
    lib.tpunet_comm_all_gather.argtypes = [u, ctypes.c_void_p, ctypes.c_void_p, u64]
    lib.tpunet_comm_all_gather.restype = i32
    lib.tpunet_comm_broadcast.argtypes = [u, ctypes.c_void_p, u64, i32]
    lib.tpunet_comm_broadcast.restype = i32
    lib.tpunet_comm_all_to_all.argtypes = [u, ctypes.c_void_p, ctypes.c_void_p, u64]
    lib.tpunet_comm_all_to_all.restype = i32
    lib.tpunet_comm_all_to_all_typed.argtypes = [
        u, ctypes.c_void_p, ctypes.c_void_p, u64, i32]
    lib.tpunet_comm_all_to_all_typed.restype = i32
    lib.tpunet_comm_iall_to_all.argtypes = [
        u, ctypes.c_void_p, ctypes.c_void_p, u64, P(u64)]
    lib.tpunet_comm_iall_to_all.restype = i32
    lib.tpunet_comm_neighbor_exchange.argtypes = [u, ctypes.c_void_p, u64, ctypes.c_void_p, u64, P(u64)]
    lib.tpunet_comm_neighbor_exchange.restype = i32
    lib.tpunet_comm_barrier.argtypes = [u]
    lib.tpunet_comm_barrier.restype = i32
    lib.tpunet_comm_iall_reduce.argtypes = [
        u, ctypes.c_void_p, ctypes.c_void_p, u64, i32, i32, P(u64),
    ]
    lib.tpunet_comm_iall_reduce.restype = i32
    lib.tpunet_comm_ticket_wait.argtypes = [u, u64]
    lib.tpunet_comm_ticket_wait.restype = i32
    lib.tpunet_comm_ticket_test.argtypes = [u, u64, P(ctypes.c_uint8)]
    lib.tpunet_comm_ticket_test.restype = i32

    lib.tpunet_c_metrics_text.argtypes = [ctypes.c_char_p, u64]
    lib.tpunet_c_metrics_text.restype = i32
    lib.tpunet_c_metrics_reset.argtypes = []
    lib.tpunet_c_metrics_reset.restype = i32
    lib.tpunet_c_trace_flush.argtypes = []
    lib.tpunet_c_trace_flush.restype = i32
    lib.tpunet_c_trace_set_dir.argtypes = [ctypes.c_char_p]
    lib.tpunet_c_trace_set_dir.restype = i32
    lib.tpunet_c_trace_span.argtypes = [
        ctypes.c_char_p, u64, u64, u64, u64, ctypes.c_char_p,
        ctypes.c_char_p, ctypes.c_int64, ctypes.c_int64]
    lib.tpunet_c_trace_span.restype = i32
    lib.tpunet_c_bridge_call.argtypes = [i32, u64]
    lib.tpunet_c_bridge_call.restype = i32
    lib.tpunet_c_bridge_chunks.argtypes = [i32, u64, u64]
    lib.tpunet_c_bridge_chunks.restype = i32
    lib.tpunet_c_bridge_minor_faults.argtypes = [i32, u64]
    lib.tpunet_c_bridge_minor_faults.restype = i32
    lib.tpunet_c_metrics_port.argtypes = []
    lib.tpunet_c_metrics_port.restype = i32
    lib.tpunet_c_serve_observe.argtypes = [i32, u64]
    lib.tpunet_c_serve_observe.restype = i32
    lib.tpunet_c_serve_queue_depth.argtypes = [i32, u64]
    lib.tpunet_c_serve_queue_depth.restype = i32
    lib.tpunet_c_qos_state.argtypes = [ctypes.c_char_p, u64]
    lib.tpunet_c_qos_state.restype = i32
    lib.tpunet_c_lane_parse.argtypes = [ctypes.c_char_p, ctypes.c_char_p, u64]
    lib.tpunet_c_lane_parse.restype = i32
    lib.tpunet_c_stripe_map.argtypes = [u64, u64, ctypes.c_char_p, u64,
                                        ctypes.c_char_p, u64]
    lib.tpunet_c_stripe_map.restype = i32
    lib.tpunet_c_qos_drr_golden.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p, u64,
    ]
    lib.tpunet_c_qos_drr_golden.restype = i32

    lib.tpunet_c_fault_inject.argtypes = [ctypes.c_char_p]
    lib.tpunet_c_fault_inject.restype = i32
    lib.tpunet_c_fault_clear.argtypes = []
    lib.tpunet_c_fault_clear.restype = i32
    lib.tpunet_c_churn_poll.argtypes = [u64, ctypes.c_int64]
    lib.tpunet_c_churn_poll.restype = i32
    lib.tpunet_c_churn_pending.argtypes = []
    lib.tpunet_c_churn_pending.restype = i32
    lib.tpunet_c_swap_poll.argtypes = [u64]
    lib.tpunet_c_swap_poll.restype = i32
    lib.tpunet_c_swap_pending.argtypes = []
    lib.tpunet_c_swap_pending.restype = i32
    lib.tpunet_c_rewire_observe.argtypes = [i32, u64]
    lib.tpunet_c_rewire_observe.restype = i32
    lib.tpunet_c_churn_event.argtypes = [i32]
    lib.tpunet_c_churn_event.restype = i32
    lib.tpunet_c_world_size.argtypes = [u64]
    lib.tpunet_c_world_size.restype = i32
    lib.tpunet_c_swap_observe.argtypes = [i32, u64]
    lib.tpunet_c_swap_observe.restype = i32
    lib.tpunet_c_swap_event.argtypes = [i32]
    lib.tpunet_c_swap_event.restype = i32
    lib.tpunet_c_weight_version.argtypes = [u64]
    lib.tpunet_c_weight_version.restype = i32
    lib.tpunet_c_flightrec_dump.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p, u64]
    lib.tpunet_c_flightrec_dump.restype = i32
    lib.tpunet_c_flightrec_stats.argtypes = [P(u64), P(u64)]
    lib.tpunet_c_flightrec_stats.restype = i32
    lib.tpunet_c_crc32c.argtypes = [ctypes.c_void_p, u64, ctypes.c_uint32]
    lib.tpunet_c_crc32c.restype = ctypes.c_uint32
    lib.tpunet_c_host_id.argtypes = []
    lib.tpunet_c_host_id.restype = u64
    lib.tpunet_c_reduce.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, u64, i32, i32,
    ]
    lib.tpunet_c_reduce.restype = i32
    lib.tpunet_c_codec_wire_bytes.argtypes = [i32, u64]
    lib.tpunet_c_codec_wire_bytes.restype = u64
    lib.tpunet_c_codec_encode.argtypes = [i32, ctypes.c_void_p, u64, ctypes.c_void_p, u64]
    lib.tpunet_c_codec_encode.restype = i32
    lib.tpunet_c_codec_decode.argtypes = [i32, ctypes.c_void_p, u64, ctypes.c_void_p]
    lib.tpunet_c_codec_decode.restype = i32

    _lib = lib
    return lib


def last_error() -> str:
    if _lib is None:
        return ""
    msg = _lib.tpunet_c_last_error()
    return msg.decode("utf-8", "replace") if msg else ""


class NativeError(RuntimeError):
    def __init__(self, code: int, op: str):
        self.code = code
        super().__init__(f"tpunet native {op} failed (code {code}): {last_error()}")


class CorruptionError(NativeError):
    """Wire payload failed its per-chunk CRC32C check (TPUNET_CRC=1).

    The affected request failed but the comm did NOT disconnect — retrying
    the collective on the same communicator is legitimate; repeated
    corruption means a bad NIC/path and warrants a rebuild."""


class ProgressTimeoutError(NativeError):
    """The progress watchdog (TPUNET_PROGRESS_TIMEOUT_MS) saw a request move
    zero bytes for a full window: the peer is alive but stuck. Classified as
    a comm failure by tpunet.train.elastic — same recovery as a dead peer."""


class VersionMismatchError(NativeError):
    """The peer speaks a different tpunet wire-framing version."""


class CodecMismatchError(NativeError):
    """The ranks of a collective group disagree on the wire compression
    codec (TPUNET_WIRE_DTYPE / wire_dtype). Raised at communicator wiring
    time on EVERY rank — before any payload could be mis-decoded — with the
    offending ranks and codecs in the message. Fix the config and rebuild
    the communicator; nothing was corrupted."""


class QosAdmissionError(NativeError):
    """QoS admission control rejected a send: the traffic class's in-flight
    byte budget (TPUNET_QOS_INFLIGHT_BYTES) is fully posted. Pure
    backpressure — NOTHING was enqueued or charged, so the send is safely
    retryable once in-flight work drains (the serve router replays it
    front-of-queue). docs/DESIGN.md "Transport QoS"."""


class RewireTimeoutError(NativeError):
    """An elastic membership rewire (tpunet.elastic.ElasticWorld) failed to
    complete inside TPUNET_REWIRE_TIMEOUT_MS — the bounded-recovery contract
    of the churn engine. The old communicator was already finalized when
    this raises, so the process holds no live comm; callers either retry
    the rewire (the membership doc may still be filling) or exit. Never a
    hang: every phase under the deadline is itself bounded (bootstrap
    timeout, membership grace window). docs/DESIGN.md "Elastic churn"."""


class WeightSwapError(NativeError):
    """A live weight publication (tpunet.serve.publish) aborted: the
    publisher or a receiver died mid-broadcast, the cross-rank CRC32C
    digest agreement failed (flip refused fleet-wide — no rank serves a
    version any other rank disagrees about), or the swap exceeded
    TPUNET_SWAP_TIMEOUT_MS. The PREVIOUS version keeps serving on every
    rank and the partial staged version was discarded, so retrying the
    publication is always safe. Never a hang: every wait inside the swap
    pipeline is bounded by the swap/bootstrap deadlines.
    docs/DESIGN.md "Live weight updates"."""


_TYPED_ERRORS = {
    TPUNET_ERR_CORRUPT: CorruptionError,
    TPUNET_ERR_TIMEOUT: ProgressTimeoutError,
    TPUNET_ERR_VERSION: VersionMismatchError,
    TPUNET_ERR_CODEC: CodecMismatchError,
    TPUNET_ERR_QOS_ADMISSION: QosAdmissionError,
    TPUNET_ERR_REWIRE: RewireTimeoutError,
    TPUNET_ERR_WEIGHT_SWAP: WeightSwapError,
}


def check(code: int, op: str) -> None:
    if code != TPUNET_OK:
        raise _TYPED_ERRORS.get(code, NativeError)(code, op)
