// tpunet — abstract point-to-point DCN transport interface.
//
// TPU-native re-design of the reference transport trait
// (reference: src/interface.rs:34-74 `trait Net`, :3-11 `BaguaNetError`,
// :13-22 `NCCLNetProperties`, :24-27 `SocketHandle`). Semantics match the
// reference: device enumeration, listen/connect/accept rendezvous, non-blocking
// isend/irecv returning request ids, `test()` polling for completion, close.
// Engines must tolerate >= 8 in-flight requests per comm (reference:
// cc/nccl_types.h:50 NCCL_NET_MAX_REQUESTS).
#ifndef TPUNET_NET_H_
#define TPUNET_NET_H_

#include <netinet/in.h>
#include <sys/socket.h>

#include <cstddef>
#include <cstdint>
#include <memory>
#include <string>
#include <thread>

namespace tpunet {

// Error taxonomy mirrors reference interface.rs:3-11 {IOError, TCPError,
// InnerError}, plus kInvalidArgument so programmer errors (stale/unknown ids,
// bad device index) are distinguishable from transport failures at the ABI,
// plus the failure-model kinds (docs/DESIGN.md "Failure model"):
//   kCorruption — a per-chunk CRC32C mismatch (TPUNET_CRC=1): the payload is
//     wrong but the stream framing is intact, so the REQUEST fails while the
//     comm stays usable (not a disconnect).
//   kTimeout — the progress watchdog (TPUNET_PROGRESS_TIMEOUT_MS) saw a
//     request move zero bytes for a full window: a live-but-stuck peer,
//     classified upstream like a dead one (elastic rebuild).
//   kVersion — the peer speaks a different tpunet wire framing version
//     (preamble magic prefix matched, version byte did not).
//   kCodec — the ranks of a collective group disagree on the wire
//     compression codec (TPUNET_WIRE_DTYPE / wire_dtype); raised at
//     communicator wiring time by the codec-byte handshake, before any
//     data could be mis-decoded (docs/DESIGN.md "Compressed collectives").
//   kQosAdmission — QoS admission control rejected a send: the traffic
//     class's in-flight byte budget (TPUNET_QOS_INFLIGHT_BYTES) is full.
//     Pure backpressure — NOTHING was enqueued; retry after in-flight work
//     drains (docs/DESIGN.md "Transport QoS").
enum class ErrorKind : int32_t {
  kOk = 0,
  kIOError = 1,
  kTCPError = 2,
  kInnerError = 3,
  kInvalidArgument = 4,
  kCorruption = 5,
  kTimeout = 6,
  kVersion = 7,
  kCodec = 8,
  kQosAdmission = 9,
};

struct Status {
  ErrorKind kind = ErrorKind::kOk;
  std::string msg;

  bool ok() const { return kind == ErrorKind::kOk; }
  static Status Ok() { return Status{}; }
  static Status IO(std::string m) { return Status{ErrorKind::kIOError, std::move(m)}; }
  static Status TCP(std::string m) { return Status{ErrorKind::kTCPError, std::move(m)}; }
  static Status Inner(std::string m) { return Status{ErrorKind::kInnerError, std::move(m)}; }
  static Status Invalid(std::string m) { return Status{ErrorKind::kInvalidArgument, std::move(m)}; }
  static Status Corruption(std::string m) { return Status{ErrorKind::kCorruption, std::move(m)}; }
  static Status Timeout(std::string m) { return Status{ErrorKind::kTimeout, std::move(m)}; }
  static Status Version(std::string m) { return Status{ErrorKind::kVersion, std::move(m)}; }
  static Status Codec(std::string m) { return Status{ErrorKind::kCodec, std::move(m)}; }
  static Status QosAdmission(std::string m) {
    return Status{ErrorKind::kQosAdmission, std::move(m)};
  }
};

// Reference: interface.rs:13-22 NCCLNetProperties.
struct NetProperties {
  std::string name;
  std::string pci_path;
  uint64_t guid = 0;
  int32_t ptr_support = 1;  // host memory only (NCCL_PTR_HOST)
  int32_t speed_mbps = 10000;
  int32_t port = 0;
  int32_t max_comms = 65536;  // reference: nthread_per_socket_backend.rs:100
};

// Opaque rendezvous handle: a serialized sockaddr, must fit the reference's
// 64-byte NCCL handle budget (reference: cc/nccl_types.h:44
// NCCL_NET_HANDLE_MAXSIZE=64, src/lib.rs:121-124 SocketHandleC).
constexpr size_t kHandleSize = 64;
struct SocketHandle {
  sockaddr_storage addr = {};  // only first kHandleSize bytes travel the wire
  socklen_t addrlen = 0;
};
static_assert(sizeof(sockaddr_in6) <= kHandleSize, "handle must fit sockaddr");

// Element types and operators of the reduction kernels (utils.h ReduceInto)
// and of a receive that reduces as it lands (Net::irecv_reduce).
enum class WireDType : uint8_t { kF32 = 0, kF64, kBF16, kI32, kI64, kU8 };
enum class WireRedOp : uint8_t { kSum = 0, kProd, kMin, kMax };

// Abstract transport. All ids are process-local opaque tokens. Thread-safety:
// all methods may be called concurrently from different threads; `accept`
// blocks until a peer connects.
class Net {
 public:
  virtual ~Net() = default;

  virtual int32_t devices() = 0;
  virtual Status get_properties(int32_t dev, NetProperties* props) = 0;

  // Bind a listening socket on device `dev`; return the rendezvous handle the
  // caller ships out-of-band to the sender, plus a listen-comm id for accept().
  virtual Status listen(int32_t dev, SocketHandle* handle, uint64_t* listen_comm) = 0;
  // Establish the multi-stream connection bundle to a remote handle
  // (nstreams data conns + 1 ctrl conn; see wire protocol in basic_engine.cc).
  virtual Status connect(int32_t dev, const SocketHandle& handle, uint64_t* send_comm) = 0;
  // Accept one sender's bundle on a listen comm. Blocks.
  virtual Status accept(uint64_t listen_comm, uint64_t* recv_comm) = 0;

  // Post a send/recv; returns immediately with a request id polled via test().
  // The caller must keep `data` alive/pinned until test() reports done
  // (reference contract: src/lib.rs:251,279).
  virtual Status isend(uint64_t send_comm, const void* data, size_t nbytes, uint64_t* request) = 0;
  // The posted recv buffer may be larger than the incoming message; the actual
  // size comes from the ctrl-stream length frame and is reported by test().
  virtual Status irecv(uint64_t recv_comm, void* data, size_t nbytes, uint64_t* request) = 0;
  // A receive that REDUCES as it lands: dst[i] = local[i] op incoming[i]
  // over the message's elements, in ReduceInto's operand order (`local` may
  // equal `dst`), so the result is bit for bit a plain receive followed by
  // ReduceInto(dst, local, received). Waited and tested like any request;
  // the bytes it reports are the message's. An engine that cannot land a
  // message this way on `recv_comm` returns kInvalidArgument without
  // posting anything, and the caller receives and reduces itself.
  virtual Status irecv_reduce(uint64_t recv_comm, void* dst, const void* local,
                              size_t nbytes, WireDType dtype, WireRedOp op,
                              uint64_t* request) {
    (void)recv_comm, (void)dst, (void)local, (void)nbytes, (void)dtype, (void)op,
        (void)request;
    return Status::Invalid("irecv_reduce is not supported on this comm");
  }
  // Poll a request. On done=true the request id is consumed (freed).
  virtual Status test(uint64_t request, bool* done, size_t* nbytes) = 0;
  // Block until the request settles, then consume it like a done test().
  // Engines override with a condvar park (a test() poll loop starves the
  // worker threads of CPU on small hosts); the base fallback polls.
  virtual Status wait(uint64_t request, size_t* nbytes) {
    bool done = false;
    while (true) {
      Status st = test(request, &done, nbytes);
      if (!st.ok() || done) return st;
      std::this_thread::yield();
    }
  }

  virtual Status close_send(uint64_t send_comm) = 0;
  virtual Status close_recv(uint64_t recv_comm) = 0;
  virtual Status close_listen(uint64_t listen_comm) = 0;

  // QoS traffic class carried by every comm this engine CONNECTS (the
  // class nibble rides the preamble flags word, so the far side's recv
  // comm adopts it — sender's class wins, like nstreams/min_chunksize).
  // Values are TrafficClass ints (qos.h: 0 latency, 1 bulk, 2 control);
  // out-of-range is clamped to bulk. Set it before connect(); default is
  // TPUNET_TRAFFIC_CLASS (bulk). docs/DESIGN.md "Transport QoS".
  virtual void set_traffic_class(int32_t cls) { (void)cls; }
  virtual int32_t traffic_class() const { return 1; /* bulk */ }
};

// Factory. Engine selected by env TPUNET_IMPLEMENT in {"BASIC" (default),
// "EPOLL"} (reference seam: src/lib.rs:20-29 BAGUA_NET_IMPLEMENT). With
// TPUNET_SHM=1 the selected engine is additionally fronted by the
// shared-memory engine: same-host peers (HostId() equality, verified in
// the SHM hello handshake) move payloads through a mmap'd per-pair ring
// segment; everything else falls through to `inner` transparently.
std::unique_ptr<Net> CreateEngine();
std::unique_ptr<Net> CreateBasicEngine();
std::unique_ptr<Net> CreateEpollEngine();
std::unique_ptr<Net> CreateShmEngine(std::unique_ptr<Net> inner);

}  // namespace tpunet

#endif  // TPUNET_NET_H_
