// tpunet SHM engine — intra-host shared-memory transport (TPUNET_SHM=1).
//
// A TPU-host pod runs R ranks per host; the TCP engines make same-host
// pairs pay full loopback cost (two kernel copies plus syscalls per chunk).
// This engine fronts a TCP engine on ONE listen socket and gives same-host
// pairs a mmap'd per-pair ring segment instead of TCP data streams:
//
//   * Rendezvous is unchanged: listen() binds the usual TCP listener whose
//     sockaddr is the 64-byte handle. connect() checks whether the handle's
//     address belongs to this host; if so it opens an SHM HELLO bundle —
//     the normal preamble with nstreams=0 and kPreambleFlagShm, so the one
//     connection doubles as the comm's ctrl stream — and negotiates the
//     segment (host id + ring size + shm_open name) on it. The receiver
//     compares HOST IDS (utils.h HostId(): TPUNET_HOST_ID override /
//     boot-id / hostname hash — the id every rank also publishes in the
//     collective bootstrap blob): equal → ack 1, map, ring engaged;
//     different (fake-host split, shared NAT address) or unmappable → ack 0
//     and BOTH sides run the comm in ctrl-TCP mode (the failover data path
//     below, engaged from byte zero) — the transparent fallback. The ack
//     rides back asynchronously: connect() returns right after the hello
//     (TCP semantics — a connect must not require the peer to be inside
//     accept(), or the collectives' connect-all-then-accept-all wiring
//     would deadlock) and the comm's scheduler thread consumes the ack
//     before the first payload byte. Cross-host handles skip all of this
//     and go straight to the inner engine.
//
//   * The data path preserves the TCP comms' LEN-frame semantics exactly:
//     every message's 8-byte big-endian length frame rides the ctrl
//     connection, chunk boundaries derive from (len, chunk size) on both
//     sides with no per-chunk metadata, and CRC32C trailers follow each
//     chunk in the ring when negotiated (kPreambleFlagCrc, sender wins).
//     Chunks move through a lock-free SPSC byte ring in the segment:
//     free-running head/tail cursors, futex parking on seq words with
//     waiter counts so a streaming steady state issues ~zero wake syscalls
//     (tpunet_shm_wakeups_total counts the ones it does), and every payload
//     byte feeds tpunet_shm_bytes_total{dir} — NOT the TCP stream/QoS byte
//     counters, which is what lets tests prove "intra-host stage moved zero
//     TCP bytes" straight off the counters.
//
//   * A receive may REDUCE as it lands (Net::irecv_reduce, posted by the
//     ring's reduce-scatter steps): the receive thread folds each chunk
//     straight out of the ring into the caller's accumulator (RingReducer),
//     so the bytes are never copied into a scratch buffer first.
//
//   * Failure containment composes unchanged: fault injection acts on the
//     segment (fault.h FaultPreMem — corrupt flips a ring byte under the
//     original-bytes CRC, stall parks against the abort flag, delay
//     sleeps), a `close` fault FAILS THE SEGMENT OVER TO TCP — the sender
//     marks the ring dead, emits the PR-1 0xFE FAILOVER marker on ctrl and
//     ships the remaining chunks (and all later messages) over the ctrl
//     TCP connection, receiver mirroring from the marker point — and peer
//     death is detected from the ctrl socket (EOF) inside every futex wait
//     slice, so "never a hang" holds even without the progress watchdog
//     (which also works: the abort hook poisons the segment like a socket
//     shutdown). QoS admission + wire credit account exactly like the TCP
//     engines (admission at isend, credit per chunk, release at
//     consumption), and the wire codec composes untouched above the engine.
#include <fcntl.h>
#include <ifaddrs.h>
#include <linux/futex.h>
#include <poll.h>
#include <string.h>
#include <sys/mman.h>
#include <sys/stat.h>
#include <sys/syscall.h>
#include <sys/socket.h>
#include <unistd.h>

#include <atomic>
#include <chrono>
#include <deque>
#include <memory>
#include <optional>
#include <set>
#include <string>
#include <thread>
#include <vector>

#include "engine_base.h"
#include "fault.h"
#include "id_map.h"
#include "tpunet/mutex.h"
#include "tpunet/net.h"
#include "tpunet/qos.h"
#include "tpunet/telemetry.h"
#include "tpunet/utils.h"
#include "wire.h"

namespace tpunet {
namespace {

constexpr uint64_t kShmMagic = 0x74707573686d3031ull;  // "tpushm01"
constexpr uint64_t kShmHdrFlagCrc = 1ull << 0;
constexpr size_t kShmRingOffset = 4096;  // header page, then ring bytes
constexpr uint32_t kSegLive = 0;
constexpr uint32_t kSegFailover = 1;  // ring dead; payload rides ctrl TCP
constexpr uint32_t kSegClosed = 2;    // comm shut down / poisoned

// Segment header. Producer-written and consumer-written state live on
// separate cache lines; the seq words are the futex parking spots (shared
// futexes — the segment is mapped by two processes).
struct ShmSegHdr {
  uint64_t magic;
  uint64_t ring_bytes;
  uint64_t flags;
  alignas(64) std::atomic<uint64_t> head;  // bytes produced (free-running)
  alignas(64) std::atomic<uint64_t> tail;  // bytes consumed (free-running)
  alignas(64) std::atomic<uint32_t> data_seq;
  std::atomic<uint32_t> data_waiters;
  alignas(64) std::atomic<uint32_t> space_seq;
  std::atomic<uint32_t> space_waiters;
  alignas(64) std::atomic<uint32_t> state;  // kSegLive / kSegFailover / kSegClosed
};
static_assert(sizeof(ShmSegHdr) <= kShmRingOffset, "header must fit its page");
static_assert(std::atomic<uint64_t>::is_always_lock_free,
              "cross-process ring cursors must be lock-free");

int FutexWait(std::atomic<uint32_t>* addr, uint32_t expect, int timeout_ms) {
  struct timespec ts = {timeout_ms / 1000, (timeout_ms % 1000) * 1000000L};
  return static_cast<int>(syscall(SYS_futex, reinterpret_cast<uint32_t*>(addr),
                                  FUTEX_WAIT, expect, &ts, nullptr, 0));
}

void FutexWakeAll(std::atomic<uint32_t>* addr) {
  syscall(SYS_futex, reinterpret_cast<uint32_t*>(addr), FUTEX_WAKE, INT32_MAX,
          nullptr, nullptr, 0);
  Telemetry::Get().OnShmWakeup();
}

// A mapped segment; the creator (sender) also owns unlinking on aborted
// handshakes — after a successful handshake the receiver has unlinked the
// name and the mapping is the only reference.
struct ShmSeg {
  ShmSegHdr* hdr = nullptr;
  uint8_t* ring = nullptr;
  size_t ring_bytes = 0;
  size_t map_bytes = 0;

  ~ShmSeg() { Release(); }
  void Release() {
    if (hdr != nullptr) ::munmap(hdr, map_bytes);
    hdr = nullptr;
    ring = nullptr;
    ring_bytes = 0;
    map_bytes = 0;
  }
  uint64_t avail() const {
    return hdr->head.load(std::memory_order_acquire) -
           hdr->tail.load(std::memory_order_acquire);
  }
  uint64_t free_bytes() const { return ring_bytes - avail(); }

  // Wrap-aware copy in/out at a free-running cursor.
  void CopyIn(uint64_t at, const uint8_t* src, size_t n) {
    size_t off = static_cast<size_t>(at % ring_bytes);
    size_t first = std::min(n, ring_bytes - off);
    memcpy(ring + off, src, first);
    if (n > first) memcpy(ring, src + first, n - first);
  }
  void CopyOut(uint64_t at, uint8_t* dst, size_t n) {
    size_t off = static_cast<size_t>(at % ring_bytes);
    size_t first = std::min(n, ring_bytes - off);
    memcpy(dst, ring + off, first);
    if (n > first) memcpy(dst + first, ring, n - first);
  }
  uint8_t ByteAt(uint64_t at) const {
    return ring[static_cast<size_t>(at % ring_bytes)];
  }
  void SetByteAt(uint64_t at, uint8_t v) {
    ring[static_cast<size_t>(at % ring_bytes)] = v;
  }

  void Publish(uint64_t new_head) {
    hdr->head.store(new_head, std::memory_order_release);
    hdr->data_seq.fetch_add(1, std::memory_order_release);
    if (hdr->data_waiters.load(std::memory_order_acquire) != 0) {
      FutexWakeAll(&hdr->data_seq);
    }
  }
  void Consume(uint64_t new_tail) {
    hdr->tail.store(new_tail, std::memory_order_release);
    hdr->space_seq.fetch_add(1, std::memory_order_release);
    if (hdr->space_waiters.load(std::memory_order_acquire) != 0) {
      FutexWakeAll(&hdr->space_seq);
    }
  }
  void MarkState(uint32_t st) {
    uint32_t cur = hdr->state.load(std::memory_order_acquire);
    // closed is terminal; failover never downgrades it.
    while (cur < st && !hdr->state.compare_exchange_weak(
                           cur, st, std::memory_order_acq_rel)) {
    }
    FutexWakeAll(&hdr->data_seq);
    FutexWakeAll(&hdr->space_seq);
  }
  uint32_t State() const { return hdr->state.load(std::memory_order_acquire); }
};

struct ShmMsg {
  uint8_t* data = nullptr;
  size_t len = 0;
  RequestPtr state;
  // A receive that reduces as it lands (ShmEngine::irecv_reduce): data =
  // local op incoming. Null for every other message.
  const uint8_t* local = nullptr;
  WireDType dtype = WireDType::kU8;
  WireRedOp op = WireRedOp::kSum;
};

// Blocking FIFO identical in spirit to the BASIC engine's Queue.
class ShmQueue {
 public:
  bool Push(ShmMsg m) {
    {
      MutexLock lk(mu_);
      if (closed_) return false;
      q_.push_back(std::move(m));
    }
    cv_.NotifyOne();
    return true;
  }
  bool Pop(ShmMsg* out) {
    MutexLock lk(mu_);
    while (!closed_ && q_.empty()) cv_.Wait(mu_);
    if (q_.empty()) return false;
    *out = std::move(q_.front());
    q_.pop_front();
    return true;
  }
  // Nonblocking pop (the pre-verdict phase multiplexes the queue against
  // the handshake-ack socket, so it cannot park in Pop).
  bool TryPop(ShmMsg* out) {
    MutexLock lk(mu_);
    if (q_.empty()) return false;
    *out = std::move(q_.front());
    q_.pop_front();
    return true;
  }
  void Close() {
    {
      MutexLock lk(mu_);
      closed_ = true;
    }
    cv_.NotifyAll();
  }

 private:
  Mutex mu_;  // leaf
  CondVar cv_;
  std::deque<ShmMsg> q_ GUARDED_BY(mu_);
  bool closed_ GUARDED_BY(mu_) = false;
};

// One direction of a same-host pair: ctrl TCP connection + the ring. The
// single scheduler thread owns ALL ctrl and ring IO for its side, so LEN
// frames, failover markers, and chunk payloads are trivially totally
// ordered — no fo_mu/ctrl_mu machinery is needed.
struct ShmComm {
  bool is_send = false;
  int ctrl_fd = -1;
  size_t chunk = 1 << 20;  // derived from (min_chunksize, ring) on BOTH sides
  bool crc = false;
  TrafficClass cls = TrafficClass::kBulk;
  ShmSeg seg;
  ShmQueue msgs;
  std::unique_ptr<std::thread> scheduler;
  std::atomic<bool> aborted{false};
  bool shm_failed = false;  // scheduler-thread-private: ring failed over /
                            // negotiated ctrl-TCP mode (nacked handshake)
  // Send side: the receiver's 1-byte handshake ack is consumed by the
  // scheduler thread (never by connect() — see the file header on why).
  // Until it arrives, messages complete OPTIMISTICALLY into the ring with
  // their LEN frames deferred (a send must complete without any peer
  // participation — the TCP kernel-buffer property the collectives'
  // connect-all-then-accept-all wiring depends on; the ring plays the
  // kernel buffer's role). The verdict then either flushes the deferred
  // LEN frames (ack: receiver drains the ring) or replays the ring content
  // interleaved with them over ctrl (nack: ctrl-TCP mode). seg_name is
  // kept so a nack can unlink the segment the receiver never opened.
  bool await_ack = false;
  // True while completed pre-verdict sends sit in the ring with their LEN
  // frames still deferred: a plain close must let the verdict flush them
  // first (see Shutdown), or data the caller saw complete is taken back.
  std::atomic<bool> preack_unflushed{false};
  std::string seg_name;
  struct Deferred {
    uint64_t len = 0;         // message length (the deferred LEN frame)
    uint64_t ring_start = 0;  // chunk-stream extent in ring cumulative bytes
    uint64_t ring_end = 0;
  };
  std::vector<Deferred> deferred;  // scheduler-thread-private
  // Recv side: where a reducing receive's chunk lands when it cannot be
  // reduced straight out of the ring (RingReducer). Scheduler-thread-private.
  ScratchBuf bounce;
  const uint64_t fork_gen = ForkGeneration();

  const std::atomic<bool>* aborted_flag() const { return &aborted; }

  // Socket-shutdown analogue: poison the segment AND the ctrl connection so
  // both sides' parked waits (futex slices, blocking ctrl reads) fail fast.
  void Abort() {
    if (aborted.exchange(true)) return;
    if (seg.hdr != nullptr) seg.MarkState(kSegClosed);
    if (ctrl_fd >= 0) ::shutdown(ctrl_fd, SHUT_RDWR);
  }

  ~ShmComm() { Shutdown(); }

  void Shutdown() {
    if (shut_) return;
    shut_ = true;
    if (ForkGeneration() != fork_gen) {
      // Forked child: the scheduler pthread never existed here — leak the
      // stale handle (any pthread call on it is UB) and only close fds.
      (void)scheduler.release();
      if (ctrl_fd >= 0) ::close(ctrl_fd);
      ctrl_fd = -1;
      return;
    }
    msgs.Close();
    // A send that completed into the ring before the handshake verdict is
    // the kernel-buffer analogue: closing a TCP socket still delivers what
    // send() accepted, so a plain close waits (bounded by the handshake
    // timeout, and not at all once aborted) for the scheduler to resolve the
    // verdict and flush the deferred LEN frames.
    if (is_send && preack_unflushed.load(std::memory_order_acquire)) {
      const uint64_t deadline_us =
          MonotonicUs() + 1000 * GetEnvU64("TPUNET_HANDSHAKE_TIMEOUT_MS", 10000);
      while (preack_unflushed.load(std::memory_order_acquire) &&
             !aborted.load(std::memory_order_acquire) &&
             MonotonicUs() < deadline_us) {
        std::this_thread::sleep_for(std::chrono::milliseconds(1));
      }
    }
    Abort();
    if (scheduler && scheduler->joinable()) scheduler->join();
    if (ctrl_fd >= 0) ::close(ctrl_fd);
    ctrl_fd = -1;
    // Sender teardown backstop: a comm shut down (poison, watchdog abort,
    // plain close) before its handshake ack resolved would otherwise leak
    // the named segment in /dev/shm forever — tmpfs is RAM. Unlinking is
    // idempotent: the receiver unlinks right after mapping (ack path) and
    // the nack path unlinks in ResolveShmVerdict, so this is ENOENT noise
    // at worst.
    if (is_send && !seg_name.empty()) ::shm_unlink(seg_name.c_str());
  }

 private:
  bool shut_ = false;
};
using ShmCommPtr = std::shared_ptr<ShmComm>;

// Both sides derive the chunk size from (sender's min_chunksize, ring
// bytes) alone — like the TCP chunk map, the ring carries no per-chunk
// metadata. A chunk plus its CRC trailer must fit in half the ring so the
// producer can stay a full chunk ahead of the consumer.
size_t ShmChunkBytes(size_t min_chunksize, size_t ring_bytes) {
  size_t cap = ring_bytes / 2 > 8 ? ring_bytes / 2 - 8 : 1;
  return std::max<size_t>(1, std::min(min_chunksize, cap));
}

// Peer-death probe on the ctrl connection, run inside futex wait slices. A
// ctrl EOF/reset means the peer process is gone — the one condition a
// memory ring cannot observe on its own. Readable DATA is normal (pipelined
// LEN frames on the recv side) and not a verdict.
bool CtrlPeerDead(int fd) {
  char b;
  ssize_t r = ::recv(fd, &b, 1, MSG_PEEK | MSG_DONTWAIT);
  if (r == 0) return true;
  if (r < 0 && errno != EAGAIN && errno != EWOULDBLOCK && errno != EINTR) return true;
  return false;
}

void FailShmMsg(ShmComm* c, const RequestPtr& state, ErrorKind kind,
                const std::string& msg) {
  state->SetError(kind, msg);
  state->completed.fetch_add(1, std::memory_order_acq_rel);
  state->NotifyIfSettled();
  (void)c;
}

// Poison: fail the current message (if any), drain + fail everything
// queued, and abort the comm.
void PoisonShm(ShmComm* c, const std::string& why) {
  c->Abort();
  c->msgs.Close();
  ShmMsg m;
  while (c->msgs.Pop(&m)) {
    FailShmMsg(c, m.state, ErrorKind::kInnerError,
               "comm broken by earlier error: " + why);
  }
}

// ---------------------------------------------------------------------------
// Send side.

// Wait for `need` bytes of ring space. kOk on success; error status when the
// comm aborted / peer died / segment closed. state==kSegFailover cannot
// happen here (only the sender sets it, and then stops calling this).
Status WaitRingSpace(ShmComm* c, uint64_t need) {
  while (true) {
    if (c->aborted.load(std::memory_order_acquire) ||
        c->seg.State() == kSegClosed) {
      return Status::IO("shm segment closed");
    }
    if (c->seg.free_bytes() >= need) return Status::Ok();
    c->seg.hdr->space_waiters.fetch_add(1, std::memory_order_acq_rel);
    uint32_t s = c->seg.hdr->space_seq.load(std::memory_order_acquire);
    if (c->seg.free_bytes() < need && c->seg.State() == kSegLive &&
        !c->aborted.load(std::memory_order_acquire)) {
      FutexWait(&c->seg.hdr->space_seq, s, 100);
    }
    c->seg.hdr->space_waiters.fetch_sub(1, std::memory_order_acq_rel);
    // Progress first, verdicts second: a consumer that frees the space and
    // THEN closes (orderly teardown) must not read as a death.
    if (c->seg.free_bytes() >= need) return Status::Ok();
    if (CtrlPeerDead(c->ctrl_fd)) {
      return Status::IO("shm peer died (ctrl connection reset mid-transfer)");
    }
  }
}

// One chunk over the ctrl TCP connection (post-failover path, both the
// marker batch and later messages). Wire layout matches a TCP data chunk:
// [payload | crc32c?] — the PR-1 retransmit framing without the seq/len
// header (chunk boundaries are deterministic on both sides).
Status SendChunkCtrl(ShmComm* c, const uint8_t* data, size_t n, bool corrupt) {
  if (!corrupt) {
    if (!c->crc) return WriteAll(c->ctrl_fd, data, n);
    uint8_t crcb[4];
    EncodeU32BE(Crc32c(data, n), crcb);
    struct iovec iov[2] = {{const_cast<uint8_t*>(data), n}, {crcb, sizeof(crcb)}};
    return WritevAll(c->ctrl_fd, iov, 2);
  }
  std::vector<uint8_t> dup(data, data + n);
  if (!dup.empty()) dup[dup.size() / 2] ^= 0x01;
  if (!c->crc) return WriteAll(c->ctrl_fd, dup.data(), dup.size());
  uint8_t crcb[4];
  EncodeU32BE(Crc32c(data, n), crcb);  // CRC over the ORIGINAL bytes
  struct iovec iov[2] = {{dup.data(), dup.size()}, {crcb, sizeof(crcb)}};
  return WritevAll(c->ctrl_fd, iov, 2);
}

// One message, sender side: LEN frame on ctrl, then chunks through the ring
// (or ctrl after a segment failover). Completion accounting is simple by
// construction: the scheduler is the only worker, so the request completes
// exactly when this returns.
Status SendOneShmMsg(ShmComm* c, const ShmMsg& m) {
  QosScheduler& qos = QosScheduler::Get();
  const bool gated = qos.wire_gate_enabled();
  uint8_t hdr8[8];
  EncodeU64BE(m.len, hdr8);
  Status s = WriteAll(c->ctrl_fd, hdr8, sizeof(hdr8));
  if (!s.ok()) return s;
  size_t nchunks = ChunkCount(m.len, c->chunk);
  size_t off = 0;
  for (size_t i = 0; i < nchunks; ++i) {
    size_t n = std::min(c->chunk, m.len - off);
    size_t wire_len = n + (c->crc ? 4 : 0);
    // Memory-transport fault gate (close/stall are RETURNED for us to
    // apply — there is no fd to shut down). Disarmed cost: one relaxed load.
    FaultAction fa = g_fault_armed.load(std::memory_order_relaxed) == 0
                         ? FaultAction::kNone
                         : FaultPreMem(true, 0, n);
    if (fa == FaultAction::kStall) {
      // Live-but-stuck: park until disarm or abort — exactly what the
      // progress watchdog exists to catch.
      while (g_fault_armed.load(std::memory_order_acquire) != 0 &&
             !c->aborted.load(std::memory_order_acquire)) {
        std::this_thread::sleep_for(std::chrono::milliseconds(5));
      }
      if (c->aborted.load(std::memory_order_acquire)) {
        return Status::IO("comm aborted during injected stall");
      }
      fa = FaultAction::kNone;
    }
    if (fa == FaultAction::kClose && c->shm_failed) {
      // Already on the ctrl path (post-failover or negotiated ctrl-TCP
      // mode): losing it is last-stream loss — poison, like the socket
      // engines' verdict.
      ::shutdown(c->ctrl_fd, SHUT_RDWR);
      return Status::IO("injected close on the shm comm's last (ctrl) path");
    }
    if (fa == FaultAction::kClose && !c->shm_failed) {
      // Segment loss: fail over to the ctrl TCP connection. Chunks [0, i)
      // of THIS message are fully in the ring (the consumer drains them
      // from shared memory unharmed); the 0xFE marker tells the receiver
      // the first chunk index that rides ctrl instead. Later messages go
      // all-ctrl. Same containment counter as a TCP stream failover.
      c->seg.MarkState(kSegFailover);
      uint8_t fr[8];
      EncodeU64BE(PackCtrlFrame(kCtrlFrameFailover, 0, i), fr);
      s = WriteAll(c->ctrl_fd, fr, sizeof(fr));
      if (!s.ok()) return s;
      c->shm_failed = true;
      Telemetry::Get().OnStreamFailover();
    }
    bool corrupt = fa == FaultAction::kCorrupt;
    if (gated && !qos.AcquireWire(c->cls, wire_len, c->aborted_flag())) {
      return Status::IO("comm aborted while awaiting QoS wire credit");
    }
    m.state->MarkWireStart(MonotonicUs());
    if (c->shm_failed) {
      s = SendChunkCtrl(c, m.data + off, n, corrupt);
      if (gated) qos.ReleaseWire(c->cls, wire_len);
      if (!s.ok()) return s;
      Telemetry::Get().OnStreamBytes(true, 0, n, static_cast<int>(c->cls));
    } else {
      s = WaitRingSpace(c, wire_len);
      if (!s.ok()) {
        if (gated) qos.ReleaseWire(c->cls, wire_len);
        return s;
      }
      uint64_t head = c->seg.hdr->head.load(std::memory_order_relaxed);
      c->seg.CopyIn(head, m.data + off, n);
      if (corrupt && n > 0) {
        // Damage the RING copy, never the caller's buffer; the trailer is
        // computed over the original bytes so TPUNET_CRC=1 catches it.
        c->seg.SetByteAt(head + n / 2, c->seg.ByteAt(head + n / 2) ^ 0x01);
      }
      if (c->crc) {
        uint8_t crcb[4];
        EncodeU32BE(Crc32c(m.data + off, n), crcb);
        c->seg.CopyIn(head + n, crcb, 4);
      }
      c->seg.Publish(head + wire_len);
      if (gated) qos.ReleaseWire(c->cls, wire_len);
      Telemetry::Get().OnShmBytes(true, n);
    }
    m.state->nbytes.fetch_add(n, std::memory_order_relaxed);
    m.state->MarkWireEnd(MonotonicUs());
    off += n;
  }
  return Status::Ok();
}

// Pre-verdict send: the whole message goes into the ring (its LEN frame is
// deferred), so completion needs no peer participation — the property the
// connect-all-then-accept-all wiring layers depend on. Returns with
// *needs_verdict set (and the message untouched) when the ring cannot hold
// it; the caller then blocks for the ack first (only the verdict can make
// room: ack → the receiver drains, nack → ctrl replay).
Status SendPreAckMsg(ShmComm* c, const ShmMsg& m, bool* needs_verdict) {
  *needs_verdict = false;
  size_t nchunks = ChunkCount(m.len, c->chunk);
  uint64_t wire_total = m.len + (c->crc ? 4 * nchunks : 0);
  if (wire_total > c->seg.free_bytes()) {
    *needs_verdict = true;
    return Status::Ok();
  }
  ShmComm::Deferred d;
  d.len = m.len;
  d.ring_start = c->seg.hdr->head.load(std::memory_order_relaxed);
  size_t off = 0;
  for (size_t i = 0; i < nchunks; ++i) {
    size_t n = std::min(c->chunk, m.len - off);
    FaultAction fa = g_fault_armed.load(std::memory_order_relaxed) == 0
                         ? FaultAction::kNone
                         : FaultPreMem(true, 0, n);
    if (fa == FaultAction::kStall) {
      while (g_fault_armed.load(std::memory_order_acquire) != 0 &&
             !c->aborted.load(std::memory_order_acquire)) {
        std::this_thread::sleep_for(std::chrono::milliseconds(5));
      }
      if (c->aborted.load(std::memory_order_acquire)) {
        return Status::IO("comm aborted during injected stall");
      }
      fa = FaultAction::kNone;
    }
    if (fa == FaultAction::kClose) {
      // No failover target exists before the verdict (the ctrl path's
      // framing depends on it) — poison, the pre-wiring corner chaos
      // matrices don't exercise.
      return Status::IO("injected close on shm segment before handshake ack");
    }
    uint64_t head = c->seg.hdr->head.load(std::memory_order_relaxed);
    c->seg.CopyIn(head, m.data + off, n);
    if (fa == FaultAction::kCorrupt && n > 0) {
      c->seg.SetByteAt(head + n / 2, c->seg.ByteAt(head + n / 2) ^ 0x01);
    }
    if (c->crc) {
      uint8_t crcb[4];
      EncodeU32BE(Crc32c(m.data + off, n), crcb);
      c->seg.CopyIn(head + n, crcb, 4);
    }
    c->seg.Publish(head + n + (c->crc ? 4 : 0));
    m.state->MarkWireStart(MonotonicUs());
    m.state->nbytes.fetch_add(n, std::memory_order_relaxed);
    m.state->MarkWireEnd(MonotonicUs());
    off += n;
  }
  d.ring_end = c->seg.hdr->head.load(std::memory_order_relaxed);
  c->deferred.push_back(d);
  c->preack_unflushed.store(true, std::memory_order_release);
  return Status::Ok();
}

// Apply the handshake verdict: flush the deferred LEN frames (ack — the
// ring content is live, byte accounting lands on the SHM counters), or
// replay [LEN | ring chunk stream] per deferred message over ctrl and drop
// the segment (nack — ctrl-TCP mode; the bytes were TCP bytes after all).
Status ResolveShmVerdict(ShmComm* c, uint8_t ack) {
  Status s;
  if (ack == 1) {
    for (const ShmComm::Deferred& d : c->deferred) {
      uint8_t hdr8[8];
      EncodeU64BE(d.len, hdr8);
      s = WriteAll(c->ctrl_fd, hdr8, sizeof(hdr8));
      if (!s.ok()) return s;
      Telemetry::Get().OnShmBytes(true, d.len);
    }
    c->deferred.clear();
    return Status::Ok();
  }
  // Nack: negotiation, not a failure — no failover counter. The receiver
  // never opened the segment, so the name is ours to unlink.
  uint8_t buf[64 << 10];
  for (const ShmComm::Deferred& d : c->deferred) {
    uint8_t hdr8[8];
    EncodeU64BE(d.len, hdr8);
    s = WriteAll(c->ctrl_fd, hdr8, sizeof(hdr8));
    if (!s.ok()) return s;
    for (uint64_t at = d.ring_start; at < d.ring_end;) {
      size_t n = static_cast<size_t>(
          std::min<uint64_t>(sizeof(buf), d.ring_end - at));
      c->seg.CopyOut(at, buf, n);
      s = WriteAll(c->ctrl_fd, buf, n);
      if (!s.ok()) return s;
      at += n;
    }
    Telemetry::Get().OnStreamBytes(true, 0, d.len, static_cast<int>(c->cls));
  }
  c->deferred.clear();
  ::shm_unlink(c->seg_name.c_str());
  c->seg.Release();
  c->shm_failed = true;
  return Status::Ok();
}

// Multiplex the pre-verdict phase: serve queued sends into the ring while
// watching the ctrl socket for the receiver's 1-byte ack. `block` demands a
// resolution (ring full / queue drained into it) — the poll then parks until
// the ack (or peer death) arrives.
Status AwaitAckStep(ShmComm* c, bool block, bool* resolved) {
  *resolved = false;
  struct pollfd pfd = {c->ctrl_fd, POLLIN, 0};
  int pr = ::poll(&pfd, 1, block ? 20 : 0);
  if (pr < 0 && errno != EINTR) {
    return Status::IO("ctrl poll failed awaiting shm handshake ack");
  }
  if (pr <= 0) return Status::Ok();
  uint8_t ack = 0;
  Status s = ReadExact(c->ctrl_fd, &ack, 1);
  if (!s.ok()) return Status::IO("shm handshake ack never arrived: " + s.msg);
  s = ResolveShmVerdict(c, ack);
  if (!s.ok()) return s;
  *resolved = true;
  return Status::Ok();
}

void ShmSendLoop(ShmComm* c) {
  // Phase 1 (handshake pending): optimistic ring sends + ack multiplexing.
  Status ps = Status::Ok();
  while (c->await_ack) {
    bool resolved = false;
    ps = AwaitAckStep(c, /*block=*/false, &resolved);
    if (!ps.ok()) break;
    if (resolved) {
      c->await_ack = false;
      break;
    }
    if (c->aborted.load(std::memory_order_acquire)) {
      ps = Status::IO("comm aborted awaiting shm handshake ack");
      break;
    }
    ShmMsg m;
    if (c->msgs.TryPop(&m)) {
      bool needs_verdict = false;
      ps = SendPreAckMsg(c, m, &needs_verdict);
      if (ps.ok() && needs_verdict) {
        // Ring cannot hold it: park for the verdict, then send normally.
        while (ps.ok() && !resolved &&
               !c->aborted.load(std::memory_order_acquire)) {
          ps = AwaitAckStep(c, /*block=*/true, &resolved);
        }
        if (ps.ok() && resolved) {
          c->await_ack = false;
          ps = SendOneShmMsg(c, m);
        } else if (ps.ok()) {
          ps = Status::IO("comm aborted awaiting shm handshake ack");
        }
      }
      if (!ps.ok()) {
        FailShmMsg(c, m.state, ps.kind, ps.msg);
        break;
      }
      m.state->completed.fetch_add(1, std::memory_order_acq_rel);
      m.state->NotifyIfSettled();
    } else {
      bool r2 = false;
      ps = AwaitAckStep(c, /*block=*/true, &r2);
      if (ps.ok() && r2) c->await_ack = false;
    }
  }
  // Flushed by the verdict, or lost to a failure: either way a close has
  // nothing left to wait for.
  c->preack_unflushed.store(false, std::memory_order_release);
  if (!ps.ok()) {
    PoisonShm(c, ps.msg);
    return;
  }
  // Phase 2: the steady-state loop.
  ShmMsg m;
  while (c->msgs.Pop(&m)) {
    Status s = SendOneShmMsg(c, m);
    if (!s.ok()) {
      FailShmMsg(c, m.state, s.kind, s.msg);
      PoisonShm(c, s.msg);
      return;
    }
    m.state->completed.fetch_add(1, std::memory_order_acq_rel);
    m.state->NotifyIfSettled();
  }
}

// ---------------------------------------------------------------------------
// Recv side.

// Wait until `need` ring bytes are available, watching for the sender's
// failover signal and peer death. *failover is set when the ring went into
// failover before producing these bytes — the caller reads the 0xFE marker
// from ctrl and switches.
Status WaitRingData(ShmComm* c, uint64_t need, bool* failover) {
  *failover = false;
  while (true) {
    if (c->seg.avail() >= need) return Status::Ok();
    if (c->aborted.load(std::memory_order_acquire) ||
        c->seg.State() == kSegClosed) {
      return Status::IO("shm segment closed");
    }
    if (c->seg.State() == kSegFailover) {
      // The sender stopped producing; everything it DID produce has been
      // consumed (chunks are published whole, so a shortfall here means
      // the missing chunk was never written).
      *failover = true;
      return Status::Ok();
    }
    c->seg.hdr->data_waiters.fetch_add(1, std::memory_order_acq_rel);
    uint32_t s = c->seg.hdr->data_seq.load(std::memory_order_acquire);
    if (c->seg.avail() < need && c->seg.State() == kSegLive &&
        !c->aborted.load(std::memory_order_acquire)) {
      FutexWait(&c->seg.hdr->data_seq, s, 100);
    }
    c->seg.hdr->data_waiters.fetch_sub(1, std::memory_order_acq_rel);
    // Progress first, verdicts second: a producer that publishes the final
    // chunks and THEN closes (orderly teardown — its requests all tested
    // done, the NCCL contract) must not read as a death; the ring bytes
    // outlive its ctrl FIN exactly like kernel socket buffers do.
    if (c->seg.avail() >= need) return Status::Ok();
    if (CtrlPeerDead(c->ctrl_fd)) {
      return Status::IO("shm peer died (ctrl connection reset mid-transfer)");
    }
  }
}

Status RecvChunkCtrl(ShmComm* c, uint8_t* data, size_t n, uint32_t* wire_crc) {
  if (!c->crc) return ReadExact(c->ctrl_fd, data, n);
  uint8_t crcb[4];
  struct iovec iov[2] = {{data, n}, {crcb, sizeof(crcb)}};
  Status s = ReadvExact(c->ctrl_fd, iov, 2);
  if (s.ok()) *wire_crc = DecodeU32BE(crcb);
  return s;
}

// Lands the chunks of a reducing receive (ShmEngine::irecv_reduce) in
// m.data = m.local op incoming, in ReduceInto's operand order, so the result
// is bit for bit a copy out of the ring and a separate reduce. Whole elements
// are reduced straight out of the ring where its bytes lie on element
// boundaries; a chunk that cannot be (CRC on, a fault armed, the ctrl path)
// lands in the comm's bounce buffer and is reduced from there. The bytes of
// an element that a chunk boundary cuts wait at the bounce's head.
class RingReducer {
 public:
  RingReducer(ShmComm* c, const ShmMsg& m)
      : dst_(m.data), local_(m.local), dtype_(m.dtype), op_(m.op),
        esize_(WireDTypeSize(m.dtype)) {
    c->bounce.reserve(c->chunk + esize_);
    bounce_ = c->bounce.data();
  }

  // Reduces the n ring bytes at cursor `at`; false, with nothing touched,
  // where an element would be read off its boundary or across the wrap.
  bool FromRing(const ShmSeg& seg, uint64_t at, size_t n) {
    const size_t pos = static_cast<size_t>(at % seg.ring_bytes);
    const size_t first = std::min(n, seg.ring_bytes - pos);
    if (pend_ != 0 || pos % esize_ != 0 || (first < n && first % esize_ != 0)) {
      return false;
    }
    Take(seg.ring + pos, first);
    if (first < n) Take(seg.ring, n - first);
    return true;
  }

  // Where the next chunk lands when FromRing declined it; Landed(n) then
  // reduces its n bytes.
  uint8_t* Slot() { return bounce_ + pend_; }
  void Landed(size_t n) {
    const size_t total = pend_ + n;
    const size_t whole = total - total % esize_;
    Reduce(bounce_, whole);
    memmove(bounce_, bounce_ + whole, total - whole);
    pend_ = total - whole;
  }

 private:
  void Take(const uint8_t* src, size_t n) {
    const size_t whole = n - n % esize_;
    Reduce(src, whole);
    memcpy(bounce_, src + whole, n - whole);
    pend_ = n - whole;
  }
  void Reduce(const uint8_t* src, size_t nbytes) {
    if (nbytes == 0) return;
    ReduceInto(dst_ + done_, local_ + done_, src, nbytes / esize_, dtype_, op_);
    Telemetry::Get().OnShmReduceBytes(nbytes);
    done_ += nbytes;
  }

  uint8_t* dst_;
  const uint8_t* local_;
  WireDType dtype_;
  WireRedOp op_;
  size_t esize_;
  uint8_t* bounce_;
  size_t done_ = 0;  // message bytes reduced
  size_t pend_ = 0;  // bytes of a cut element at the bounce's head
};

Status RecvOneShmMsg(ShmComm* c, const ShmMsg& m) {
  uint8_t hdr8[8];
  Status s = ReadExact(c->ctrl_fd, hdr8, sizeof(hdr8));
  if (!s.ok()) return s;
  uint64_t target = DecodeU64BE(hdr8);
  if (target >= kMaxCtrlLen) {
    return Status::Inner("bogus shm ctrl frame — peer desynchronized");
  }
  if (target > m.len) {
    return Status::Inner("incoming message (" + std::to_string(target) +
                         "B) exceeds posted recv buffer (" +
                         std::to_string(m.len) + "B)");
  }
  size_t len = static_cast<size_t>(target);
  size_t nchunks = ChunkCount(len, c->chunk);
  std::optional<RingReducer> reducer;
  if (m.local != nullptr) reducer.emplace(c, m);
  size_t off = 0;
  for (size_t i = 0; i < nchunks; ++i) {
    size_t n = std::min(c->chunk, len - off);
    size_t wire_len = n + (c->crc ? 4 : 0);
    FaultAction fa = g_fault_armed.load(std::memory_order_relaxed) == 0
                         ? FaultAction::kNone
                         : FaultPreMem(false, 0, n);
    if (fa == FaultAction::kStall) {
      while (g_fault_armed.load(std::memory_order_acquire) != 0 &&
             !c->aborted.load(std::memory_order_acquire)) {
        std::this_thread::sleep_for(std::chrono::milliseconds(5));
      }
      if (c->aborted.load(std::memory_order_acquire)) {
        return Status::IO("comm aborted during injected stall");
      }
      fa = FaultAction::kNone;
    }
    if (fa == FaultAction::kClose) {
      // Receiver-side segment loss has no failover lever (the sender drives
      // the ring) — poison, the socket engines' last-stream verdict.
      return Status::IO("injected close on shm segment (receive side)");
    }
    uint32_t wire_crc = 0;
    bool from_ring = !c->shm_failed;
    if (from_ring) {
      bool failover = false;
      s = WaitRingData(c, wire_len, &failover);
      if (!s.ok()) return s;
      if (failover) {
        // The 0xFE marker names the first chunk index riding ctrl; chunks
        // before it were fully published (and already consumed above).
        uint8_t fr[8];
        s = ReadExact(c->ctrl_fd, fr, sizeof(fr));
        if (!s.ok()) return s;
        uint64_t frame = DecodeU64BE(fr);
        if ((frame >> 56) != kCtrlFrameFailover ||
            (frame & 0xffffffffffffull) != i) {
          return Status::Inner(
              "shm failover marker mismatch (protocol desync)");
        }
        c->shm_failed = true;
        from_ring = false;
      }
    }
    // A copied chunk lands in the caller's buffer, or in the bounce buffer
    // of a reducing receive.
    uint8_t* land = reducer ? reducer->Slot() : m.data + off;
    bool reduced = false;
    m.state->MarkWireStart(MonotonicUs());
    if (from_ring) {
      uint64_t tail = c->seg.hdr->tail.load(std::memory_order_relaxed);
      reduced = reducer && !c->crc && fa == FaultAction::kNone &&
                reducer->FromRing(c->seg, tail, n);
      if (!reduced) c->seg.CopyOut(tail, land, n);
      if (c->crc) {
        uint8_t crcb[4];
        c->seg.CopyOut(tail + n, crcb, 4);
        wire_crc = DecodeU32BE(crcb);
      }
      c->seg.Consume(tail + wire_len);
    } else {
      s = RecvChunkCtrl(c, land, n, &wire_crc);
      if (!s.ok()) return s;
    }
    if (fa == FaultAction::kCorrupt && n > 0) {
      land[n / 2] ^= 0x01;  // wire damage before verification
    }
    if (c->crc && wire_crc != Crc32c(land, n)) {
      // Integrity failure is a REQUEST error, not a disconnect: the chunk
      // framing is intact (exactly chunk+trailer was consumed), so the
      // comm keeps working for subsequent messages — the socket engines'
      // contract, preserved on the ring.
      Telemetry::Get().OnCrcError();
      m.state->SetError(ErrorKind::kCorruption,
                        "CRC32C mismatch on shm segment: payload corrupted "
                        "in transit");
    } else {
      // A failed request reduces nothing more: its chunks keep the framing.
      if (reducer && !reduced && !m.state->failed.load(std::memory_order_acquire)) {
        reducer->Landed(n);
      }
      if (from_ring) {
        Telemetry::Get().OnShmBytes(false, n);
      } else {
        Telemetry::Get().OnStreamBytes(false, 0, n, static_cast<int>(c->cls));
      }
    }
    m.state->nbytes.fetch_add(n, std::memory_order_relaxed);
    m.state->MarkWireEnd(MonotonicUs());
    off += n;
  }
  return Status::Ok();
}

void ShmRecvLoop(ShmComm* c) {
  ShmMsg m;
  while (c->msgs.Pop(&m)) {
    Status s = RecvOneShmMsg(c, m);
    if (!s.ok()) {
      FailShmMsg(c, m.state, s.kind, s.msg);
      PoisonShm(c, s.msg);
      return;
    }
    m.state->completed.fetch_add(1, std::memory_order_acq_rel);
    m.state->NotifyIfSettled();
  }
}

// ---------------------------------------------------------------------------
// Engine.

// Every address this host owns (including loopback): the connect-side
// locality test. The final verdict is the handshake's host-id comparison —
// this set only decides whether attempting the handshake is worth a
// connection (NAT'd or routed handles that LOOK local get nacked there).
std::set<std::string> LocalAddressSet() {
  std::set<std::string> out;
  struct ifaddrs* ifa = nullptr;
  if (getifaddrs(&ifa) != 0) return out;
  for (struct ifaddrs* p = ifa; p != nullptr; p = p->ifa_next) {
    if (p->ifa_addr == nullptr) continue;
    int fam = p->ifa_addr->sa_family;
    if (fam != AF_INET && fam != AF_INET6) continue;
    sockaddr_storage ss = {};
    memcpy(&ss, p->ifa_addr,
           fam == AF_INET ? sizeof(sockaddr_in) : sizeof(sockaddr_in6));
    out.insert(SockaddrToString(ss, AddrLenForFamily(ss)));
  }
  freeifaddrs(ifa);
  return out;
}

std::string AddrOnly(const sockaddr_storage& ss) {
  // SockaddrToString prints host:port; strip the port so listener handles
  // (ephemeral ports) compare against interface addresses (port 0).
  std::string s = SockaddrToString(ss, AddrLenForFamily(ss));
  size_t colon = s.rfind(':');
  return colon == std::string::npos ? s : s.substr(0, colon);
}

// Inner-engine ids are tagged with this bit in the ids we hand out, so every
// call dispatches to the right owner without a lookup table.
constexpr uint64_t kInnerIdBit = 1ull << 62;

class ShmEngine : public EngineBase {
 public:
  explicit ShmEngine(std::unique_ptr<Net> inner)
      : inner_(std::move(inner)),
        adopter_(dynamic_cast<BundleAdopter*>(inner_.get())),
        ring_bytes_(GetEnvU64("TPUNET_SHM_RING_BYTES", 8 << 20)) {
    if (ring_bytes_ < (64 << 10)) ring_bytes_ = 64 << 10;
    if (ring_bytes_ > (1ull << 30)) ring_bytes_ = 1ull << 30;
    for (const std::string& a : LocalAddressSet()) {
      size_t colon = a.rfind(':');
      local_addrs_.insert(colon == std::string::npos ? a : a.substr(0, colon));
    }
  }

  ~ShmEngine() override {
    for (auto& c : send_comms_.DrainAll()) c->Shutdown();
    for (auto& c : recv_comms_.DrainAll()) c->Shutdown();
    WakeAllListens();
  }

  void set_traffic_class(int32_t cls) override {
    EngineBase::set_traffic_class(cls);
    inner_->set_traffic_class(cls);  // inner connects carry the class too
  }

  Status connect(int32_t dev, const SocketHandle& handle, uint64_t* send_comm) override {
    Status sdev = CheckDev(dev);
    if (!sdev.ok()) return sdev;
    if (adopter_ == nullptr || local_addrs_.count(AddrOnly(handle.addr)) == 0) {
      return InnerConnect(dev, handle, send_comm);
    }
    // SHM attempt: one preamble'd connection (nstreams=0 + the SHM flag)
    // that becomes the comm's ctrl stream, then the segment handshake on
    // it. ANY nack or handshake failure falls back to plain TCP — locality
    // looked right but the peer knows better (fake-host split, TPUNET_SHM
    // disabled remotely is a config error caught elsewhere).
    std::vector<int> data_fds;
    int ctrl_fd = -1;
    Status s = ConnectBundle(nics_, dev, handle, 0, min_chunksize_,
                             PreambleFlags() | kPreambleFlagShm, &data_fds, &ctrl_fd);
    if (!s.ok()) return InnerConnect(dev, handle, send_comm);
    std::string name = "/tpunet-" + std::to_string(::getpid()) + "-" +
                       std::to_string(next_id_.fetch_add(1)) + "-" +
                       std::to_string(RandomBundleId() & 0xffffff);
    auto comm = std::make_shared<ShmComm>();
    comm->is_send = true;
    comm->ctrl_fd = ctrl_fd;
    comm->crc = crc_;
    comm->cls = static_cast<TrafficClass>(traffic_class());
    comm->chunk = ShmChunkBytes(min_chunksize_, ring_bytes_);
    s = CreateSegment(name, comm->crc, &comm->seg);
    if (!s.ok()) {
      ::close(ctrl_fd);
      comm->ctrl_fd = -1;
      return InnerConnect(dev, handle, send_comm);
    }
    // Hello: [host_id u64 | ring_bytes u64 | name_len u64 | name]. The ack
    // comes back ASYNCHRONOUSLY (read by the scheduler thread) — a connect
    // must not require the peer to be inside accept() already, or the
    // collectives' connect-all-then-accept-all wiring would deadlock.
    std::vector<uint8_t> hello(24 + name.size());
    EncodeU64BE(HostId(), hello.data());
    EncodeU64BE(ring_bytes_, hello.data() + 8);
    EncodeU64BE(name.size(), hello.data() + 16);
    memcpy(hello.data() + 24, name.data(), name.size());
    s = WriteAll(ctrl_fd, hello.data(), hello.size());
    if (!s.ok()) {
      ::shm_unlink(name.c_str());
      ::close(ctrl_fd);
      comm->ctrl_fd = -1;
      return InnerConnect(dev, handle, send_comm);
    }
    comm->await_ack = true;
    comm->seg_name = name;
    comm->scheduler = std::make_unique<std::thread>(ShmSendLoop, comm.get());
    uint64_t id = next_id_.fetch_add(1);
    send_comms_.Put(id, comm);
    *send_comm = id;
    return Status::Ok();
  }

  Status accept(uint64_t listen_comm, uint64_t* recv_comm) override {
    while (true) {
      PartialBundle b;
      Status s = AcceptBundleOn(listen_comm, &b);
      if (!s.ok()) return s;
      if ((b.flags & kPreambleFlagShm) == 0) {
        if (adopter_ == nullptr) {
          b.CloseAll();
          return Status::Inner("inner engine cannot adopt TCP bundles");
        }
        uint64_t inner_id = 0;
        s = adopter_->AdoptBundle(b, &inner_id);
        if (!s.ok()) return s;
        *recv_comm = inner_id | kInnerIdBit;
        return Status::Ok();
      }
      // SHM hello on our listener. A nack (host mismatch, bad segment)
      // keeps accepting — the sender redials over TCP and that bundle
      // lands here next.
      int fd = b.ctrl_fd;
      b.ctrl_fd = -1;
      b.CloseAll();
      int hs_ms = static_cast<int>(GetEnvU64("TPUNET_HANDSHAKE_TIMEOUT_MS", 10000));
      uint8_t hdr24[24];
      s = ReadExactDeadline(fd, hdr24, sizeof(hdr24), hs_ms);
      if (!s.ok()) {
        ::close(fd);
        continue;
      }
      uint64_t peer_host = DecodeU64BE(hdr24);
      uint64_t ring_bytes = DecodeU64BE(hdr24 + 8);
      uint64_t name_len = DecodeU64BE(hdr24 + 16);
      if (name_len == 0 || name_len > 255) {
        ::close(fd);
        continue;
      }
      std::string name(name_len, '\0');
      s = ReadExactDeadline(fd, &name[0], name_len, hs_ms);
      if (!s.ok()) {
        ::close(fd);
        continue;
      }
      auto comm = std::make_shared<ShmComm>();
      uint8_t ack = 0;
      if (peer_host == HostId() &&
          MapSegment(name, ring_bytes, &comm->seg).ok()) {
        ack = 1;
      }
      Status ws = WriteAll(fd, &ack, 1);
      if (!ws.ok()) {
        ::close(fd);
        continue;  // peer died mid-handshake; keep serving the listener
      }
      comm->is_send = false;
      comm->ctrl_fd = fd;
      // Nacked (fake-host split / unmappable segment): both sides run the
      // comm in ctrl-TCP mode from byte zero — the transparent fallback the
      // forced-split tests exercise. The sender unlinks the segment.
      comm->shm_failed = ack != 1;
      // Sender's chunk-map inputs win, like the TCP preamble contract
      // (its CRC flag and min_chunksize ride the preamble; the ring size
      // rode the hello), so both modes derive identical chunk geometry.
      comm->crc = (b.flags & kPreambleFlagCrc) != 0;
      comm->cls = static_cast<TrafficClass>(PreambleClassOf(b.flags));
      comm->chunk = ShmChunkBytes(b.min_chunksize, static_cast<size_t>(ring_bytes));
      comm->scheduler = std::make_unique<std::thread>(ShmRecvLoop, comm.get());
      uint64_t id = next_id_.fetch_add(1);
      recv_comms_.Put(id, comm);
      *recv_comm = id;
      return Status::Ok();
    }
  }

  Status isend(uint64_t send_comm, const void* data, size_t nbytes, uint64_t* request) override {
    if (send_comm & kInnerIdBit) {
      Status s = inner_->isend(send_comm & ~kInnerIdBit, data, nbytes, request);
      if (s.ok()) *request |= kInnerIdBit;
      return s;
    }
    ShmCommPtr c;
    if (!send_comms_.Get(send_comm, &c)) {
      return Status::Invalid("unknown send comm " + std::to_string(send_comm));
    }
    if (ForkGeneration() != c->fork_gen) {
      return Status::Inner("send comm created before fork(); its threads do not exist here");
    }
    uint64_t admitted = 0;
    Status as = QosScheduler::Get().AdmitMessage(c->cls, nbytes, &admitted);
    if (!as.ok()) return as;
    auto state = std::make_shared<RequestState>();
    state->qos_cls = static_cast<uint8_t>(c->cls);
    state->qos_admitted = admitted;
    state->t_post_us = MonotonicUs();
    state->total.store(1, std::memory_order_release);  // one completion unit
    ArmWatchdog(state, c);
    uint64_t id = next_id_.fetch_add(1);
    requests_.Put(id, state);
    if (!c->msgs.Push(ShmMsg{const_cast<uint8_t*>(static_cast<const uint8_t*>(data)),
                             nbytes, state})) {
      FailShmMsg(c.get(), state, ErrorKind::kInnerError, "send comm is poisoned");
    }
    *request = id;
    return Status::Ok();
  }

  Status irecv(uint64_t recv_comm, void* data, size_t nbytes, uint64_t* request) override {
    if (recv_comm & kInnerIdBit) {
      Status s = inner_->irecv(recv_comm & ~kInnerIdBit, data, nbytes, request);
      if (s.ok()) *request |= kInnerIdBit;
      return s;
    }
    return PostRecv(recv_comm, ShmMsg{static_cast<uint8_t*>(data), nbytes, nullptr}, request);
  }

  // The receive thread reduces each chunk as it lands (RingReducer); the
  // inner engine's comms cannot.
  Status irecv_reduce(uint64_t recv_comm, void* dst, const void* local, size_t nbytes,
                      WireDType dtype, WireRedOp op, uint64_t* request) override {
    if ((recv_comm & kInnerIdBit) || WireDTypeSize(dtype) == 0) {
      return Net::irecv_reduce(recv_comm, dst, local, nbytes, dtype, op, request);
    }
    ShmMsg m{static_cast<uint8_t*>(dst), nbytes, nullptr};
    m.local = static_cast<const uint8_t*>(local != nullptr ? local : dst);
    m.dtype = dtype;
    m.op = op;
    return PostRecv(recv_comm, std::move(m), request);
  }

  Status test(uint64_t request, bool* done, size_t* nbytes) override {
    if (request & kInnerIdBit) return inner_->test(request & ~kInnerIdBit, done, nbytes);
    RequestPtr state;
    if (!requests_.Get(request, &state)) {
      return Status::Invalid("unknown request " + std::to_string(request));
    }
    if (state->failed.load(std::memory_order_acquire)) {
      if (!state->Done()) {
        *done = false;
        return Status::Ok();
      }
      state->ReleaseQosAdmission();
      requests_.Erase(request);
      return Status{state->ErrKind(), "request failed: " + state->ErrorMsg()};
    }
    *done = state->Done();
    if (*done) {
      if (nbytes) *nbytes = state->nbytes.load(std::memory_order_acquire);
      RecordRequestStages(state);
      state->ReleaseQosAdmission();
      requests_.Erase(request);
    }
    return Status::Ok();
  }

  Status wait(uint64_t request, size_t* nbytes) override {
    if (request & kInnerIdBit) return inner_->wait(request & ~kInnerIdBit, nbytes);
    return WaitIn(requests_, request, nbytes);
  }

  Status close_send(uint64_t send_comm) override {
    if (send_comm & kInnerIdBit) return inner_->close_send(send_comm & ~kInnerIdBit);
    ShmCommPtr c;
    if (!send_comms_.Take(send_comm, &c)) {
      return Status::Invalid("unknown send comm " + std::to_string(send_comm));
    }
    c->Shutdown();
    return Status::Ok();
  }

  Status close_recv(uint64_t recv_comm) override {
    if (recv_comm & kInnerIdBit) return inner_->close_recv(recv_comm & ~kInnerIdBit);
    ShmCommPtr c;
    if (!recv_comms_.Take(recv_comm, &c)) {
      return Status::Invalid("unknown recv comm " + std::to_string(recv_comm));
    }
    c->Shutdown();
    return Status::Ok();
  }

 private:
  Status PostRecv(uint64_t recv_comm, ShmMsg m, uint64_t* request) {
    ShmCommPtr c;
    if (!recv_comms_.Get(recv_comm, &c)) {
      return Status::Invalid("unknown recv comm " + std::to_string(recv_comm));
    }
    if (ForkGeneration() != c->fork_gen) {
      return Status::Inner("recv comm created before fork(); its threads do not exist here");
    }
    auto state = std::make_shared<RequestState>();
    state->t_post_us = MonotonicUs();
    state->total.store(1, std::memory_order_release);
    ArmWatchdog(state, c);
    uint64_t id = next_id_.fetch_add(1);
    requests_.Put(id, state);
    m.state = state;
    if (!c->msgs.Push(std::move(m))) {
      FailShmMsg(c.get(), state, ErrorKind::kInnerError, "recv comm is poisoned");
    }
    *request = id;
    return Status::Ok();
  }

  Status InnerConnect(int32_t dev, const SocketHandle& handle, uint64_t* send_comm) {
    uint64_t inner_id = 0;
    Status s = inner_->connect(dev, handle, &inner_id);
    if (!s.ok()) return s;
    *send_comm = inner_id | kInnerIdBit;
    return Status::Ok();
  }

  void ArmWatchdog(const RequestPtr& state, const ShmCommPtr& c) {
    if (watchdog_ms_ == 0) return;
    std::weak_ptr<ShmComm> wc = c;
    state->on_stall = [wc] {
      if (auto p = wc.lock()) p->Abort();
    };
  }

  Status CreateSegment(const std::string& name, bool crc, ShmSeg* seg) {
    int fd = ::shm_open(name.c_str(), O_CREAT | O_EXCL | O_RDWR, 0600);
    if (fd < 0) {
      return Status::IO("shm_open(" + name + "): " + strerror(errno));
    }
    size_t total = kShmRingOffset + static_cast<size_t>(ring_bytes_);
    if (::ftruncate(fd, static_cast<off_t>(total)) != 0) {
      ::close(fd);
      ::shm_unlink(name.c_str());
      return Status::IO("ftruncate shm segment: " + std::string(strerror(errno)));
    }
    void* p = ::mmap(nullptr, total, PROT_READ | PROT_WRITE, MAP_SHARED, fd, 0);
    ::close(fd);
    if (p == MAP_FAILED) {
      ::shm_unlink(name.c_str());
      return Status::IO("mmap shm segment: " + std::string(strerror(errno)));
    }
    memset(p, 0, kShmRingOffset);
    seg->hdr = new (p) ShmSegHdr();
    seg->hdr->magic = kShmMagic;
    seg->hdr->ring_bytes = ring_bytes_;
    seg->hdr->flags = crc ? kShmHdrFlagCrc : 0;
    seg->ring = static_cast<uint8_t*>(p) + kShmRingOffset;
    seg->ring_bytes = static_cast<size_t>(ring_bytes_);
    seg->map_bytes = total;
    return Status::Ok();
  }

  Status MapSegment(const std::string& name, uint64_t ring_bytes, ShmSeg* seg) {
    if (ring_bytes < (64 << 10) || ring_bytes > (1ull << 30)) {
      return Status::Invalid("shm ring size out of range");
    }
    int fd = ::shm_open(name.c_str(), O_RDWR, 0600);
    if (fd < 0) {
      return Status::IO("shm_open(" + name + "): " + strerror(errno));
    }
    struct stat st = {};
    size_t total = kShmRingOffset + static_cast<size_t>(ring_bytes);
    if (::fstat(fd, &st) != 0 || static_cast<size_t>(st.st_size) < total) {
      ::close(fd);
      return Status::IO("shm segment smaller than advertised");
    }
    void* p = ::mmap(nullptr, total, PROT_READ | PROT_WRITE, MAP_SHARED, fd, 0);
    ::close(fd);
    if (p == MAP_FAILED) {
      return Status::IO("mmap shm segment: " + std::string(strerror(errno)));
    }
    // The name's job is done: unlink now so the segment dies with the last
    // mapping and a crashed pair never leaks /dev/shm entries.
    ::shm_unlink(name.c_str());
    seg->hdr = static_cast<ShmSegHdr*>(p);
    seg->ring = static_cast<uint8_t*>(p) + kShmRingOffset;
    seg->ring_bytes = static_cast<size_t>(ring_bytes);
    seg->map_bytes = total;
    if (seg->hdr->magic != kShmMagic || seg->hdr->ring_bytes != ring_bytes) {
      ::munmap(p, total);
      seg->hdr = nullptr;
      seg->ring = nullptr;
      return Status::IO("shm segment header mismatch");
    }
    return Status::Ok();
  }

  std::unique_ptr<Net> inner_;
  BundleAdopter* adopter_;
  uint64_t ring_bytes_;
  std::set<std::string> local_addrs_;
  IdMap<ShmCommPtr> send_comms_;
  IdMap<ShmCommPtr> recv_comms_;
  IdMap<RequestPtr> requests_;
};

}  // namespace

std::unique_ptr<Net> CreateShmEngine(std::unique_ptr<Net> inner) {
  return std::make_unique<ShmEngine>(std::move(inner));
}

}  // namespace tpunet
