// tpunet — OS helpers: NIC discovery, link speed, socket IO, chunk math.
// Reference behavior being reproduced: src/utils.rs (find_interfaces :32-130,
// get_net_if_speed :7-23, nonblocking_write_all/read_exact :132-178,
// chunk_size :200-205, parse_user_pass_and_addr :180-198).
#ifndef TPUNET_UTILS_H_
#define TPUNET_UTILS_H_

#include <sys/socket.h>
#include <sys/uio.h>

#include <cstddef>
#include <cstdint>
#include <string>
#include <vector>

#include "tpunet/net.h"

namespace tpunet {

struct NicInfo {
  std::string name;
  sockaddr_storage addr = {};
  socklen_t addrlen = 0;
  std::string pci_path;   // resolved from /sys/class/net/<if>/device
  int32_t speed_mbps = 0; // from /sys/class/net/<if>/speed
};

// Enumerate non-loopback up interfaces with an IPv4/IPv6 address, dedup by
// name, honoring:
//   TPUNET_SOCKET_IFNAME / NCCL_SOCKET_IFNAME — "^a,b" prefix-exclude,
//     "=a,b" exact-include, "a,b" prefix-include; default exclude "^docker,lo"
//     (reference: utils.rs:37-49).
//   TPUNET_SOCKET_FAMILY / NCCL_SOCKET_FAMILY — AF_INET / AF_INET6 restrict
//     (reference: utils.rs:33-36,100-103).
std::vector<NicInfo> FindInterfaces();

// Link speed in Mbps from /sys/class/net/<if>/speed; 10000 when unreadable
// (reference: utils.rs:7-23, default :8).
int32_t GetNetIfSpeed(const std::string& ifname);

// max(ceil(total/n), min_chunksize) — both peers compute identical chunk
// boundaries from (len, min_chunksize, nstreams) alone, so the wire carries no
// per-chunk metadata (reference: utils.rs:200-205).
size_t ChunkSize(size_t total, size_t min_chunksize, size_t n);
// Number of chunks a message of `total` bytes splits into (0 for total==0).
size_t ChunkCount(size_t total, size_t chunksize);

// Weighted-round-robin slot table for lane striping (docs/DESIGN.md "Lanes
// & adaptive striping"): stream i appears weights[i] times per period
// (sum of weights), interleaved by stride scheduling — at every slot the
// stream with the largest accumulated credit wins (ties break to the lowest
// index), so heavy lanes spread across the period instead of bursting.
// Deterministic: identical weights produce identical tables on both sides
// of a comm, which (with the shared rotating cursor) is what keeps the
// sender's and receiver's chunk->stream maps symmetric without any
// per-chunk wire metadata. Equal weights degenerate to [0, 1, ..., n-1] —
// exactly the uniform rotation. Weights of 0 are treated as 1 (a lane may
// be demoted to the floor but never unscheduled: floor-1 keeps its rate
// measurable for recovery).
std::vector<uint8_t> BuildWrrSlots(const std::vector<uint32_t>& weights);

// ---- Wire-syscall accounting (tpunet_engine_syscalls_total{op,dir}) -------
// Every send/recv-family syscall the engines issue on their data paths bumps
// one relaxed process-wide counter, indexed by the syscall actually made
// (writev/readv are issued as sendmsg/recvmsg so flags apply). The counters
// are what makes the zero-copy work measurable: syscalls/MiB is a number the
// 1-vCPU sandbox cannot noise out the way it noises GB/s.
enum IoOp { kIoSend = 0, kIoRecv = 1, kIoSendmsg = 2, kIoRecvmsg = 3, kIoOpCount = 4 };
void CountIoSyscall(IoOp op);
uint64_t IoSyscallCount(IoOp op);
void ResetIoSyscallCounts();

// Blocking write/read of exactly n bytes, retrying on EINTR/partial IO.
// A read of 0 bytes means EOF -> error (reference: utils.rs:168-171).
// If `spin` is true the fd is assumed nonblocking and we busy-poll on
// EWOULDBLOCK with sched_yield (the reference's only mode, utils.rs:132-178);
// the default blocking mode is our TPU-host-friendly improvement (no 100% CPU
// burn on a shared trainer host). ReadExact passes MSG_WAITALL so a blocking
// chunk read is ONE syscall, not one per kernel-buffer refill — the recv-side
// half of the syscalls/MiB budget (docs/DESIGN.md "Data path").
Status WriteAll(int fd, const void* buf, size_t n, bool spin = false);
Status ReadExact(int fd, void* buf, size_t n, bool spin = false);

// Vectored variants: move every byte described by iov[0..iovcnt) in as few
// sendmsg/recvmsg syscalls as possible (one, in the common case — e.g. a
// chunk payload and its CRC32C trailer coalesce instead of paying separate
// syscalls). The iov array is MUTATED as the cursor advances across partial
// IO; zero-length entries are permitted. Semantics otherwise match
// WriteAll/ReadExact (EINTR retry, spin busy-poll, EOF -> error on read;
// reads use MSG_WAITALL).
Status WritevAll(int fd, struct iovec* iov, int iovcnt, bool spin = false);
Status ReadvExact(int fd, struct iovec* iov, int iovcnt, bool spin = false);

// Read exactly n bytes with a hard wall-clock deadline over the WHOLE read
// (poll + MSG_DONTWAIT recv) — unlike SO_RCVTIMEO, which restarts on every
// byte and lets a slow-loris client stretch a 40-byte read to 40x the
// timeout. Returns IOError on timeout or EOF.
Status ReadExactDeadline(int fd, void* buf, size_t n, int timeout_ms);

// CRC32C (Castagnoli, the iSCSI/ext4 polynomial) over `n` bytes, seeded with
// `crc` (0 for a fresh checksum; chain calls to checksum discontiguous
// buffers). Hardware-accelerated via SSE4.2 when the CPU has it, slicing-by-8
// software fallback otherwise. Golden vector: crc32c("123456789") ==
// 0xE3069283 (RFC 3720 B.4). Used for the per-chunk wire-integrity trailer
// (TPUNET_CRC=1) on data streams.
uint32_t Crc32c(const void* data, size_t n, uint32_t crc = 0);

// ---- Reduction kernels (the collectives' post-wire stage) -----------------
// Elementwise dst[i] = a[i] op b[i] for the wire dtypes. dst may alias a
// (the classic in-place accumulate); the out-of-place collectives pass
// a = caller's sendbuf so no staging copy ever exists. Dispatch is runtime:
// AVX2 lanes for f32/bf16 when the CPU has them (TPUNET_REDUCE_SIMD=0
// forces scalar for bisection), scalar otherwise — the scalar and SIMD
// paths are BITWISE identical, including NaN/inf propagation and bf16
// round-to-nearest-even (pinned by tests/test_wire_vectored.py goldens).
// Above a size threshold the work fans out over a persistent fork-join pool
// (TPUNET_REDUCE_THREADS total shards incl. the caller; 0 = auto), so the
// reduce of ring chunk k keeps pace with the wire moving chunk k+1.
// Every call adds n * element-size to the tpunet_reduce_bytes_total counter.
// WireDType and WireRedOp are declared in net.h.
size_t WireDTypeSize(WireDType d);
void ReduceInto(void* dst, const void* a, const void* b, size_t n,
                WireDType dtype, WireRedOp op);
uint64_t ReduceBytesTotal();
void ResetReduceBytesTotal();

// ---- Wire codecs (compressed ring collectives) ----------------------------
// On-the-wire compression for f32 collective payloads (docs/DESIGN.md
// "Compressed collectives"): the ring encodes each chunk right before isend
// and runs a fused decode+reduce right after irecv, so the ACCUMULATOR stays
// f32 and quantization error enters only at wire hops (EQuARX-style), never
// compounds in the running sum. Two codecs:
//   kBF16 — truncate-with-RNE to bfloat16 (the SAME integer
//     round-to-nearest-even arithmetic as the bf16 reduce kernels, so the
//     wire values are bit-identical to a bf16 cast); 2 bytes/element.
//   kI8 — block-scaled int8: per kI8CodecBlock(=256)-element block, one f32
//     scale amax/127 followed by the rounded int8 quotients. Max elementwise
//     error per wire hop is amax_block/254 (half a quantization step; see
//     DESIGN.md for the derivation). n + 4*ceil(n/256) bytes.
// Dispatch is runtime like ReduceInto: AVX2 bf16 lanes when the CPU has them
// (gated by the same TPUNET_REDUCE_SIMD=0 bisection switch), scalar
// otherwise — bitwise identical either way. Every encode/decode call feeds
// the tpunet_codec_bytes_total{codec,dir} counters plus the payload-byte
// totals behind the tpunet_codec_wire_ratio gauge.
enum class WireCodec : uint8_t { kF32 = 0, kBF16 = 1, kI8 = 2 };
constexpr int kWireCodecCount = 3;
constexpr size_t kI8CodecBlock = 256;  // elements per int8 scale block

// "f32" / "bf16" / "int8" <-> WireCodec. Parse returns false on unknown.
bool ParseWireCodec(const std::string& name, WireCodec* out);
const char* WireCodecName(WireCodec c);

// Encoded byte count for n f32 elements (n*4 for kF32 passthrough).
size_t CodecWireBytes(WireCodec c, size_t n);
// Encode n f32 elements into dst (CodecWireBytes(c, n) bytes).
void CodecEncode(WireCodec c, const float* src, uint8_t* dst, size_t n);
// Decode a wire buffer back to n f32 elements.
void CodecDecode(WireCodec c, const uint8_t* wire, float* dst, size_t n);
// Fused decode+reduce: dst[i] = local[i] op decode(wire)[i], all f32.
// local == nullptr means dst itself (in-place accumulate).
void CodecDecodeReduce(WireCodec c, float* dst, const float* local,
                       const uint8_t* wire, size_t n, WireRedOp op);
// Fused decode+reduce+re-encode for the ring's RS->AG handoff:
//   t       = local op decode(wire)        (f32 accumulate, as above)
//   enc_out = encode(t)                    (the AG phase's step-0 send)
//   dst     = decode(encode(t))            (the QUANTIZED accumulator)
// dst holds the decode of what peers will receive, so every rank
// materializes bit-identical slice values without the AG phase paying a
// separate encode + decode pass over the slice (that pair measured ~1/3 of
// the whole compressed-allreduce overhead). local == nullptr means dst.
void CodecDecodeReduceQuantize(WireCodec c, float* dst, const float* local,
                               const uint8_t* wire, uint8_t* enc_out,
                               size_t n, WireRedOp op);

// Counters behind tpunet_codec_bytes_total{codec,dir} and the
// tpunet_codec_wire_ratio gauge. dir: 0 = tx (encode), 1 = rx (decode).
// Payload totals count the f32 bytes the encoded form stands in for.
uint64_t CodecBytesTotal(WireCodec c, int dir);
uint64_t CodecPayloadBytesTotal(int dir);
void ResetCodecBytesTotals();

// Growable 64-byte-aligned scratch that never zero-fills: reserve() grows
// capacity WITHOUT initializing or preserving contents (it is a landing
// buffer for wire bytes / reduce partials — std::vector::resize would pay an
// O(capacity) zero-fill pass plus first-touch faults for data about to be
// overwritten, the copy class the zero-staging collectives exist to avoid).
// Alignment keeps the SIMD reduce on aligned loads when slices line up.
class ScratchBuf {
 public:
  ScratchBuf() = default;
  ~ScratchBuf();
  ScratchBuf(const ScratchBuf&) = delete;
  ScratchBuf& operator=(const ScratchBuf&) = delete;
  ScratchBuf(ScratchBuf&& o) noexcept : p_(o.p_), cap_(o.cap_) {
    o.p_ = nullptr;
    o.cap_ = 0;
  }
  ScratchBuf& operator=(ScratchBuf&& o) noexcept {
    swap(o);
    return *this;
  }
  uint8_t* data() { return p_; }
  size_t capacity() const { return cap_; }
  void reserve(size_t n);
  void swap(ScratchBuf& o) {
    uint8_t* tp = p_;
    size_t tc = cap_;
    p_ = o.p_;
    cap_ = o.cap_;
    o.p_ = tp;
    o.cap_ = tc;
  }

 private:
  uint8_t* p_ = nullptr;
  size_t cap_ = 0;
};

// "user:pass@host:port" -> (user, pass, addr); user/pass empty when absent
// (reference: utils.rs:180-198).
struct UserPassAddr {
  std::string user, pass, addr;
};
bool ParseUserPassAndAddr(const std::string& s, UserPassAddr* out);

// 8-byte big-endian frame helpers (wire protocol ids + length frames;
// reference: nthread_per_socket_backend.rs:327,395-397 to_be_bytes).
void EncodeU64BE(uint64_t v, uint8_t out[8]);
uint64_t DecodeU64BE(const uint8_t in[8]);

// Env helpers.
std::string GetEnv(const char* name, const std::string& fallback = "");
uint64_t GetEnvU64(const char* name, uint64_t fallback);

// CLOCK_MONOTONIC in microseconds — the shared clock for telemetry stage
// timestamps and trace spans. Monotonic is machine-wide (per-boot), so spans
// from different processes on ONE host share a timeline; cross-host traces
// are aligned by collective tags in merge_traces() instead.
uint64_t MonotonicUs();

// Stable host identity: FNV-1a hash of TPUNET_HOST_ID when set (the
// fake-host override that splits one box into testable "hosts"), else of
// /proc/sys/kernel/random/boot_id (per-boot-unique, shared by every
// process/container on the host), else of gethostname(). Never 0. Two
// processes report the same id iff they can share a memory segment — the
// locality verdict behind the SHM transport handshake and the hierarchical
// collective's host grouping (docs/DESIGN.md "Intra-host shared memory").
uint64_t HostId();

// Fork-generation counter: bumps in the child after every fork() (via a
// pthread_atfork handler registered on first call). Threads do not survive
// fork, so anything owning a thread records ForkGeneration() at creation and
// treats a mismatch as "my thread does not exist in this process" — fail fast
// / leak the handle instead of hanging in a queue no one drains or joining a
// pthread that never existed here.
uint64_t ForkGeneration();

// Socket helpers.
Status SetNodelay(int fd);
Status SetNonblocking(int fd);
// Grow SO_SNDBUF/SO_RCVBUF to TPUNET_SOCKET_BUFSIZE bytes (0 = leave kernel
// autotuning alone, the default). Best-effort: the kernel clamps to
// net.core.{w,r}mem_max and never errors the connection over it.
void ApplySocketBufsize(int fd);
// TCP keepalive for dead-peer detection (TPUNET_KEEPALIVE_{IDLE_S,INTVL_S,
// CNT}; idle 0 disables). Best-effort.
void ApplyKeepalive(int fd);
std::string SockaddrToString(const sockaddr_storage& ss, socklen_t len);

}  // namespace tpunet

#endif  // TPUNET_UTILS_H_
