// tpunet BASIC engine — thread-per-stream multi-stream TCP transport.
//
// TPU-native re-design of the reference's default engine
// (reference: src/implement/nthread_per_socket_backend.rs). Behavioral
// contract reproduced:
//   * per send/recv comm: 1 scheduler thread + nstreams data-stream threads,
//     each owning one TCP connection (reference :103-237, :336-361).
//   * every message is split into chunks of max(ceil(len/nstreams),
//     min_chunksize) and chunks are assigned round-robin starting at a
//     per-comm cursor that persists ACROSS messages (reference :393,412) —
//     the fairness mechanism: even 1-chunk messages rotate streams.
//   * sender and receiver compute identical chunk boundaries + assignment
//     from (len, min_chunksize, nstreams) alone, so the wire carries no
//     per-chunk header; TCP per-stream ordering makes this correct.
//   * per message the ctrl stream carries an 8-byte big-endian length frame
//     (reference :395-397/:494-502); the receiver may post a larger buffer
//     and learns the true size from this frame.
//   * completion = bytes handed to the kernel socket buffer, not peer-ACKed.
//   * request lifecycle: isend/irecv return an id, test() polls, done
//     consumes the id.
//
// Deliberate improvements over the reference (documented deltas):
//   * Wire preamble carries bundle id + nstreams + min_chunksize (wire.h) —
//     concurrent senders on one listen socket, no config divergence, magic
//     check. Shared with the EPOLL engine, so the two engines interoperate
//     (the reference's BASIC/TOKIO were wire-incompatible).
//   * Blocking sockets by default instead of the reference's nonblocking
//     busy-poll spin (reference utils.rs:132-178) — a TPU host shares cores
//     with the trainer; TPUNET_SPIN=1 restores spin mode for latency hunts.
//   * No global engine mutex (reference lib.rs:14-16): ids resolve through
//     sharded maps, test() touches only atomics.
//   * Request ids are freed on completion (reference leaked them:
//     cc/bagua_net.cc:111-121).
#include <errno.h>
#include <poll.h>
#include <string.h>
#include <sys/socket.h>
#include <unistd.h>

#include <atomic>
#include <chrono>
#include <deque>
#include <memory>
#include <mutex>
#include <thread>
#include <vector>

#include "engine_base.h"
#include "fault.h"
#include "id_map.h"
#include "tpunet/mutex.h"
#include "tpunet/net.h"
#include "tpunet/telemetry.h"
#include "tpunet/utils.h"
#include "wire.h"

namespace tpunet {
namespace {

// Number of lazy recvs currently parked process-wide. Lets a send-side
// wait() park on its condvar outright (no 50ms upgrade sweeps) when there
// is nothing to upgrade. Global (not per-engine) so Comm::Shutdown can
// maintain it; cross-engine conservatism is harmless.
std::atomic<int> g_lazy_parked{0};

bool DebugOn() {
  static const bool on = GetEnvU64("TPUNET_DEBUG", 0) != 0;
  return on;
}
#define TPUNET_DBG(...) do { if (DebugOn()) { fprintf(stderr, "[eng %d] ", (int)getpid()); fprintf(stderr, __VA_ARGS__); fprintf(stderr, "\n"); } } while (0)

// MPSC blocking queue with close semantics (stands in for the reference's
// flume channels, nthread:224-226). Pop returns false only when closed AND
// drained, so close_send/close_recv still flush queued work.
template <typename T>
class Queue {
 public:
  // Returns false (and does not enqueue) once the queue is closed — the
  // caller owns failing the item. This is how a poisoned comm rejects new
  // messages without a parked fail-sink thread.
  bool Push(T t) {
    {
      MutexLock lk(mu_);
      if (closed_) return false;
      q_.push_back(std::move(t));
    }
    cv_.NotifyOne();
    return true;
  }
  bool Pop(T* out) {
    MutexLock lk(mu_);
    while (!closed_ && q_.empty()) cv_.Wait(mu_);
    if (q_.empty()) return false;
    *out = std::move(q_.front());
    q_.pop_front();
    return true;
  }
  // Nonblocking drain (failover: a retiring worker discards its queued
  // tasks — the per-stream records are the authoritative copy).
  bool TryPop(T* out) {
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
  Mutex mu_;  // leaf: nothing else is acquired while held
  CondVar cv_;
  std::deque<T> q_ GUARDED_BY(mu_);
  bool closed_ GUARDED_BY(mu_) = false;
};

struct ChunkTask {
  uint8_t* data = nullptr;  // send: source bytes; recv: destination bytes
  size_t len = 0;
  uint64_t seq = 0;  // per-stream chunk sequence number (failover protocol)
  RequestPtr state;
};

// Failover bookkeeping: one record per chunk logically assigned to a data
// stream. The sender retains records until the owning message settles (so a
// NACKed stream's undelivered chunks can be retransmitted over the ctrl
// connection); the receiver retains them until the chunk is fully read (so
// a FAILOVER marker knows which buffers the retransmit batch fills).
struct ChunkRec {
  uint64_t seq = 0;
  uint8_t* data = nullptr;
  size_t len = 0;
  RequestPtr state;
  bool written = false;  // sender only: payload fully handed to the kernel
};

struct Msg {
  uint8_t* data = nullptr;
  size_t len = 0;
  RequestPtr state;
};

struct Comm;

// One data stream: a TCP connection owned by one worker thread.
struct StreamWorker {
  int fd = -1;
  size_t idx = 0;  // data-stream index (for per-stream fairness counters)
  Comm* comm = nullptr;
  Queue<ChunkTask> tasks;
  std::thread thread;
};

// A send or recv comm: ctrl connection + scheduler thread + stream workers.
struct Comm {
  bool is_send = false;
  int ctrl_fd = -1;
  size_t nstreams = 0;
  size_t min_chunksize = 0;
  bool spin = false;
  bool crc = false;  // per-chunk CRC32C trailers (negotiated in the preamble)
  // QoS traffic class (sender's engine class, carried to the receiver in
  // the preamble nibble — docs/DESIGN.md "Transport QoS"). Drives the
  // wire-credit gate on send workers and per-class byte accounting on both
  // sides; immutable after wiring.
  TrafficClass cls = TrafficClass::kBulk;
  std::vector<std::unique_ptr<StreamWorker>> workers;
  Queue<Msg> msgs;
  std::unique_ptr<std::thread> scheduler;

  // ---- Failover state (single-stream degradation; docs/DESIGN.md) -------
  // fo_mu guards chunk assignment (cursor, per-stream seq counters,
  // records, dead/retired bits) AND every ctrl-stream write, so message
  // length frames and FAILOVER markers are totally ordered — that ordering
  // is what lets both sides switch their chunk→stream rotation at the same
  // point. Uncontended in steady state: one acquisition per message, not
  // per chunk... (chunks are dispatched under the same acquisition).
  // Ordering: ctrl_mu may be held when fo_mu is taken (failover marker
  // processing), never the reverse.
  Mutex fo_mu ACQUIRED_AFTER(ctrl_mu);
  // dead: IO on the stream has failed locally (or a NACK told the sender);
  // no further tasks go to its worker, but the assignment rotation still
  // includes it — records accumulate — until the FAILOVER marker retires it.
  // retired: excluded from the rotation from the marker point in ctrl order.
  std::vector<uint8_t> stream_dead GUARDED_BY(fo_mu);
  std::vector<uint8_t> stream_retired GUARDED_BY(fo_mu);
  size_t dead_count GUARDED_BY(fo_mu) = 0;
  // Sender: the reverse ctrl direction is gone (peer closed it or died), so
  // no NACK can arrive any more and a failing stream must poison, not wait.
  bool nack_reader_gone GUARDED_BY(fo_mu) = false;
  std::vector<std::deque<ChunkRec>> recs GUARDED_BY(fo_mu);  // per-stream, seq-ordered
  std::vector<uint64_t> next_seq GUARDED_BY(fo_mu);  // chunks ever assigned per stream
  std::vector<uint64_t> done_seq GUARDED_BY(fo_mu);  // receiver: chunks fully read
  // Receiver ctrl-read ownership: the scheduler, a lazy-recv caller, and a
  // failed worker acting as ctrl pump never read the ctrl fd concurrently.
  // A LEN frame read by the pump before its message is popped is stashed
  // here (consumed by the next owner, preserving frame↔message pairing).
  Mutex ctrl_mu;
  bool has_pending_frame GUARDED_BY(ctrl_mu) = false;
  uint64_t pending_frame GUARDED_BY(ctrl_mu) = 0;
  // Sender: reverse-ctrl reader parked on the (normally silent) receiver→
  // sender direction of the ctrl connection, waiting for NACK frames.
  std::unique_ptr<std::thread> nack_reader;

  // ---- Lane striping (docs/DESIGN.md "Lanes & adaptive striping") --------
  // `lanes` flips the chunk→stream rotation from the uniform cursor onto a
  // weighted-round-robin slot table derived from `weights`. Negotiated via
  // kPreambleFlagLanes (sender-wins): both sides run the slot-table walk or
  // neither does, so the maps stay symmetric. Weights change only via
  // epoch-stamped WEIGHTS ctrl frames, emitted/applied under fo_mu in the
  // same total order as message LEN frames — re-striping therefore lands
  // exactly at message boundaries and every downstream mechanism (CRC
  // framing, failover records, QoS credits, codec chunk sizing) composes
  // unchanged.
  bool lanes = false;
  bool lane_adapt = false;          // sender runs the adaptation loop
  uint64_t lane_adapt_us = 100000;  // TPUNET_LANE_ADAPT_MS
  std::vector<uint32_t> base_weights;  // configured lane weights (TPUNET_LANES)
  std::vector<uint32_t> weights GUARDED_BY(fo_mu);
  std::vector<uint8_t> slots GUARDED_BY(fo_mu);  // WRR slot table
  uint64_t stripe_epoch GUARDED_BY(fo_mu) = 0;
  uint64_t next_adapt_us GUARDED_BY(fo_mu) = 0;
  // Per-lane wire-service accounting fed by the send workers (relaxed
  // atomics — the adaptation tick drains them under fo_mu). busy_us counts
  // the full chunk service time including kernel backpressure and injected
  // delays, which is what makes the measured rate track the path a TCP_INFO
  // delivery-rate sample cannot see through on loopback.
  struct LaneIo {
    std::atomic<uint64_t> busy_us{0};
    std::atomic<uint64_t> bytes{0};
    std::atomic<uint64_t> rate_ewma_bps{0};
  };
  std::unique_ptr<LaneIo[]> lane_io;  // sized nstreams before threads start

  bool Aborted() const { return aborted_.load(std::memory_order_acquire); }
  // For QosScheduler::AcquireWire's bounded park: a worker waiting for wire
  // credit must notice comm shutdown without a dedicated wakeup channel.
  const std::atomic<bool>* aborted_flag() const { return &aborted_; }
  // Inline fast path state (PERF_NOTES: caller->scheduler->worker hops cost
  // ~0.4ms per 1MiB message on a 1-core host). `inflight` counts messages
  // not yet fully settled; when it reads 0 the scheduler is idle and every
  // prior byte is in the kernel, so the caller thread may take the
  // scheduler's role for its own message (ctrl frame + chunk dispatch)
  // without reordering the wire. `cursor` is the chunk->stream rotation,
  // shared by scheduler and inline path — never concurrently: the inline
  // path only runs at inflight==0, and the release/acquire pair on
  // `inflight` orders the scheduler's last cursor write before the caller's
  // read. Callers are single-threaded per comm (NCCL proxy contract; our
  // collectives layer likewise).
  std::atomic<uint64_t> inflight{0};
  // All cursor touches happen inside the fo_mu-held assignment sections
  // (AssignStreamIdx), so the annotation is fo_mu even though the
  // inline-path handoff above is what really orders scheduler vs caller.
  uint64_t cursor GUARDED_BY(fo_mu) = 0;
  // Lazy recv slot: an irecv posted on an idle comm parks here; its wait()
  // executes the ctrl read + data read inline on the caller thread (saving
  // two hops and the completion wakeup). test() or a later irecv upgrades
  // it onto the scheduler queue instead.
  Mutex lazy_mu;
  Msg lazy_msg GUARDED_BY(lazy_mu);
  bool has_lazy GUARDED_BY(lazy_mu) = false;
  uint64_t lazy_req GUARDED_BY(lazy_mu) = 0;
  // Threads do not survive fork(): a mismatch means this comm's scheduler /
  // workers never existed in this process (see Shutdown and the engine's
  // isend/irecv fail-fast).
  const uint64_t fork_gen = ForkGeneration();

  ~Comm() { Shutdown(); }

  // On any stream IO error, poison every connection in the comm so sibling
  // workers blocked mid-chunk fail fast and all requests quiesce — without
  // this, a single dead stream would leave test() hanging on the survivors.
  void AbortStreams() {
    if (aborted_.exchange(true)) return;
    for (auto& w : workers) {
      if (w->fd >= 0) ::shutdown(w->fd, SHUT_RDWR);
    }
    if (ctrl_fd >= 0) ::shutdown(ctrl_fd, SHUT_RDWR);
  }

  void Shutdown() {
    if (shut_) return;
    shut_ = true;
    // A lazy recv parked here would otherwise never execute; fail it so a
    // post-close wait() errors instead of hanging.
    {
      MutexLock lk(lazy_mu);
      if (has_lazy) {
        lazy_msg.state->SetError("comm closed with pending lazy recv");
        lazy_msg.state->total.store(0, std::memory_order_release);
        inflight.fetch_sub(1, std::memory_order_release);
        lazy_msg.state->NotifyIfSettled();
        lazy_msg = Msg{};
        has_lazy = false;
        g_lazy_parked.fetch_sub(1, std::memory_order_relaxed);
      }
    }
    if (ForkGeneration() != fork_gen) {
      // Forked child: scheduler/worker pthreads never existed here and the
      // queue mutexes may have been captured mid-lock at fork. Leak the
      // thread handles (any pthread call on their stale ids is UB) and only
      // close this process's copies of the fds.
      (void)scheduler.release();
      (void)nack_reader.release();
      for (auto& w : workers) {
        if (w->fd >= 0) ::close(w->fd);
        (void)w.release();
      }
      workers.clear();
      if (ctrl_fd >= 0) ::close(ctrl_fd);
      ctrl_fd = -1;
      return;
    }
    msgs.Close();
    // By the NCCL contract every request has been test()ed done before close,
    // so scheduler/workers are idle in Pop and the shutdown()s below are
    // no-ops data-wise. If the contract was violated (peer stalled/died with
    // bytes in flight), SHUT_RDWR wakes threads blocked in kernel send/recv —
    // a hang would otherwise be permanent since std::thread has no timed join.
    AbortStreams();
    if (scheduler && scheduler->joinable()) scheduler->join();
    if (nack_reader && nack_reader->joinable()) nack_reader->join();
    for (auto& w : workers) w->tasks.Close();
    for (auto& w : workers) {
      if (w->thread.joinable()) w->thread.join();
    }
    for (auto& w : workers) {
      if (w->fd >= 0) ::close(w->fd);
      w->fd = -1;
    }
    if (ctrl_fd >= 0) ::close(ctrl_fd);
    ctrl_fd = -1;
  }

 private:
  std::atomic<bool> aborted_{false};
  bool shut_ = false;
};
using CommPtr = std::shared_ptr<Comm>;

// ---------------------------------------------------------------------------
// Worker / scheduler loops.

// Chunk completion accounting shared by worker loops AND the failover
// retransmit paths: whoever settles the message (last chunk) releases the
// comm's inflight slot, re-arming the inline fast path.
void AccountChunkDone(Comm* c, const RequestPtr& state, size_t len) {
  if (len > 0) {
    // Stage-latency stamps: every completion path (worker, lazy, failover
    // retransmit) marks last-wire here; the CAS-from-0 start is a fallback
    // for paths that never stamped the true IO start (retransmits).
    uint64_t now = MonotonicUs();
    state->MarkWireStart(now);
    state->MarkWireEnd(now);
  }
  state->nbytes.fetch_add(len, std::memory_order_relaxed);
  uint64_t prior = state->completed.fetch_add(1, std::memory_order_acq_rel);
  uint64_t tot = state->total.load(std::memory_order_acquire);
  TPUNET_DBG("chunk done len=%zu completed=%llu/%llu fail=%d", len, (unsigned long long)(prior+1), (unsigned long long)tot, (int)state->failed.load());
  if (prior + 1 >= tot) {
    c->inflight.fetch_sub(1, std::memory_order_release);
  }
  state->NotifyIfSettled();
}

void FinishChunk(StreamWorker* w, ChunkTask& t) { AccountChunkDone(w->comm, t.state, t.len); }

// ---- Chunk assignment (fo_mu held) ----------------------------------------

// Rotating-cursor pick over the NON-RETIRED streams in index order. With no
// failures this is exactly the historical workers[cursor % nstreams]; after
// a failover marker both sides hold an identical retired set and an
// identical cursor (assignments are identical in ctrl order), so the
// reduced-width rotation stays symmetric.
size_t AssignStreamIdx(Comm* c) REQUIRES(c->fo_mu) {
  if (c->lanes && !c->slots.empty()) {
    // Weighted rotation: walk the WRR slot table from the shared cursor,
    // skipping retired streams (post-failover re-stripe of the survivors).
    // Both sides advance the cursor identically — including the skips —
    // because retirement and weight epochs land at the same points in ctrl
    // order, so the maps stay symmetric with zero per-chunk wire metadata.
    for (size_t tries = 0; tries <= c->slots.size(); ++tries) {
      size_t s = c->slots[c->cursor % c->slots.size()];
      c->cursor += 1;
      if (!c->stream_retired[s]) return s;
    }
    return 0;  // unreachable: alive >= 1 and every stream has >= 1 slot
  }
  size_t alive = c->nstreams - [&] {
    size_t r = 0;
    for (size_t i = 0; i < c->nstreams; ++i) r += c->stream_retired[i] ? 1 : 0;
    return r;
  }();
  size_t pick = c->cursor % alive;
  c->cursor += 1;  // persists across messages — fairness rotation
  for (size_t i = 0; i < c->nstreams; ++i) {
    if (c->stream_retired[i]) continue;
    if (pick == 0) return i;
    --pick;
  }
  return 0;  // unreachable: alive >= 1 is an invariant (last loss poisons)
}

// Drop front records whose chunk was written AND whose message has settled
// — the app may free those buffers after test(), so they are no longer
// retransmittable (a NACK that still needs one becomes a typed poison, the
// accepted kernel-buffered-bytes-lost race).
void PruneRecs(Comm* c, size_t idx) REQUIRES(c->fo_mu) {
  auto& q = c->recs[idx];
  while (!q.empty() && q.front().written &&
         (q.front().state->Done() || q.front().state->failed.load(std::memory_order_acquire))) {
    q.pop_front();
  }
}

// Assign one chunk: record it, and hand it to the worker unless the stream
// is locally dead (then the record alone carries it until the failover
// marker retransmits or poisons).
void AssignChunk(Comm* c, uint8_t* data, size_t n, const RequestPtr& state)
    REQUIRES(c->fo_mu) {
  size_t idx = AssignStreamIdx(c);
  uint64_t seq = c->next_seq[idx]++;
  if (c->is_send) PruneRecs(c, idx);
  c->recs[idx].push_back(ChunkRec{seq, data, n, state, false});
  if (!c->stream_dead[idx]) {
    c->workers[idx]->tasks.Push(ChunkTask{data, n, seq, state});
  }
}

// Sender: flag a record's payload as kernel-accepted (completion-counted).
// Returns false when the record is GONE — a concurrent NACK failover
// already claimed this chunk (retransmitted it over ctrl and accounted it),
// so the worker must NOT count it again. A missing record can mean nothing
// else: prune only removes records already marked written.
bool MarkWritten(Comm* c, size_t idx, uint64_t seq) {
  MutexLock lk(c->fo_mu);
  for (auto& r : c->recs[idx]) {
    if (r.seq == seq) {
      r.written = true;
      return true;
    }
  }
  return false;
}

// Receiver: a chunk fully arrived on its assigned stream.
void PopRec(Comm* c, size_t idx, uint64_t seq) {
  MutexLock lk(c->fo_mu);
  auto& q = c->recs[idx];
  if (!q.empty() && q.front().seq == seq) q.pop_front();
  c->done_seq[idx] = seq + 1;
}

// ---- Chunk wire IO (vectored) ----------------------------------------------
// One sendmsg/recvmsg per chunk: payload and (when negotiated) the 4-byte
// CRC32C trailer ride a single syscall instead of two, and the recv side's
// MSG_WAITALL read is one syscall per chunk instead of one per kernel-buffer
// refill. Wire bytes are IDENTICAL to the segmented writes (payload||crc) —
// v3 peers interop either way; tests/test_wire_vectored.py captures the
// frames and pins that.

Status SendChunkWire(int fd, const uint8_t* data, size_t len, bool crc, bool spin) {
  if (!crc) return WriteAll(fd, data, len, spin);
  uint8_t crcb[4];
  EncodeU32BE(Crc32c(data, len), crcb);
  struct iovec iov[2] = {{const_cast<uint8_t*>(data), len}, {crcb, sizeof(crcb)}};
  return WritevAll(fd, iov, 2, spin);
}

// With CRC: trailer is read into *wire_crc alongside the payload. The CRC is
// computed over the ORIGINAL bytes by the sender, so a fault-injected wire
// flip (applied by the caller after this returns) is detectable.
Status RecvChunkWire(int fd, uint8_t* data, size_t len, bool crc, bool spin,
                     uint32_t* wire_crc) {
  if (!crc) return ReadExact(fd, data, len, spin);
  uint8_t crcb[4];
  struct iovec iov[2] = {{data, len}, {crcb, sizeof(crcb)}};
  Status s = ReadvExact(fd, iov, 2, spin);
  if (s.ok()) *wire_crc = DecodeU32BE(crcb);
  return s;
}

// ---- Stream failure handling ----------------------------------------------

// Sender-side data-stream IO failure. Returns true when failover is engaged
// (the worker retires quietly: drain the queue, keep the records, wait for
// the receiver's NACK); false when the comm must poison (already aborted,
// single-stream comm, or last surviving stream).
bool SenderStreamFailed(Comm* c, StreamWorker* w) {
  {
    MutexLock lk(c->fo_mu);
    if (c->Aborted() || c->nstreams == 1 || c->nack_reader_gone) return false;
    if (!c->stream_dead[w->idx]) {
      if (c->dead_count + 1 >= c->nstreams) return false;  // last stream: poison
      c->stream_dead[w->idx] = 1;
      c->dead_count += 1;
      Telemetry::Get().OnStreamFailover();
      // Force the receiver's blocked read to notice promptly even when the
      // failure was one-sided (FIN/RST): its NACK is what unblocks us.
      ::shutdown(w->fd, SHUT_RDWR);
      TPUNET_DBG("send stream %zu dead, awaiting NACK", w->idx);
    }
  }
  ChunkTask d;
  while (w->tasks.TryPop(&d)) {
  }  // records are the authoritative copy
  return true;
}

// Receiver-side data-stream IO failure: same verdict logic; on failover the
// caller sends the NACK naming how many chunks it fully read off the stream
// (== the first per-stream seq it still needs).
bool ReceiverStreamFailed(Comm* c, StreamWorker* w) {
  {
    MutexLock lk(c->fo_mu);
    if (c->Aborted() || c->nstreams == 1) return false;
    if (!c->stream_dead[w->idx]) {
      if (c->dead_count + 1 >= c->nstreams) return false;
      c->stream_dead[w->idx] = 1;
      c->dead_count += 1;
      Telemetry::Get().OnStreamFailover();
      uint8_t frame[8];
      EncodeU64BE(PackCtrlFrame(kCtrlFrameNack, w->idx, c->done_seq[w->idx]), frame);
      Status ns = WriteAll(c->ctrl_fd, frame, sizeof(frame), c->spin);
      if (!ns.ok()) return false;  // ctrl is gone too: poison
      TPUNET_DBG("recv stream %zu dead, NACK sent (done_seq=%llu)", w->idx,
                 (unsigned long long)c->done_seq[w->idx]);
    }
  }
  ChunkTask d;
  while (w->tasks.TryPop(&d)) {
  }
  return true;
}

void PoisonAndDrainQueue(Comm* c, const std::string& why);  // defined below

void SendWorkerLoop(StreamWorker* w, bool spin) {
  Comm* c = w->comm;
  QosScheduler& qos = QosScheduler::Get();
  const bool gated = qos.wire_gate_enabled();
  ChunkTask t;
  while (w->tasks.Pop(&t)) {
    // QoS wire gate: hold credit for this chunk's wire bytes before they
    // may enter the kernel socket buffer. The DRR pump (qos.cc) decides
    // grant order across classes, so a latency-class chunk on another comm
    // waits behind at most the window of already-granted bytes — never
    // behind this comm's whole backlog. Credit is returned right after the
    // write syscall on EVERY path (the kernel buffer drains on its own).
    size_t wire_len = t.len + (c->crc ? 4 : 0);
    if (gated && !qos.AcquireWire(c->cls, wire_len, c->aborted_flag())) {
      // Comm aborted while parked for credit: same verdict as an IO error
      // on an aborted comm — settle the chunk and drain.
      t.state->SetError("comm aborted while awaiting QoS wire credit");
      FinishChunk(w, t);
      PoisonAndDrainQueue(c, "comm aborted while awaiting QoS wire credit");
      continue;
    }
    t.state->MarkWireStart(MonotonicUs());  // queue stage ends at first chunk IO
    // Lane service clock: spans the fault gate AND the (blocking) write, so
    // injected delays and kernel backpressure both land in the measured
    // per-lane rate — the adaptation signal TCP_INFO's burst-window
    // delivery-rate estimate cannot see on loopback.
    uint64_t lane_t0 = c->lanes ? MonotonicUs() : 0;
    FaultAction fa = FaultCheck(true, w->idx, w->fd, t.len);
    Status s;
    if (fa == FaultAction::kCorrupt) {
      // Damage the wire copy, never the caller's buffer; the CRC trailer is
      // computed over the ORIGINAL bytes so TPUNET_CRC=1 catches the flip.
      std::vector<uint8_t> dup(t.data, t.data + t.len);
      if (!dup.empty()) dup[dup.size() / 2] ^= 0x01;
      if (c->crc) {
        uint8_t crcb[4];
        EncodeU32BE(Crc32c(t.data, t.len), crcb);
        struct iovec iov[2] = {{dup.data(), dup.size()}, {crcb, sizeof(crcb)}};
        s = WritevAll(w->fd, iov, 2, spin);
      } else {
        s = WriteAll(w->fd, dup.data(), dup.size(), spin);
      }
    } else {
      s = SendChunkWire(w->fd, t.data, t.len, c->crc, spin);
    }
    if (gated) qos.ReleaseWire(c->cls, wire_len);
    if (!s.ok()) {
      if (SenderStreamFailed(c, w)) return;  // failover: records carry the rest
      t.state->SetError(s.msg);
      FinishChunk(w, t);
      // Full poison (not just AbortStreams): any records orphaned by an
      // earlier mid-failover stream death must settle too, or test() would
      // hold their requests forever waiting to quiesce.
      PoisonAndDrainQueue(c, s.msg);
      continue;
    }
    if (!MarkWritten(c, w->idx, t.seq)) {
      // A racing NACK failover already retransmitted and ACCOUNTED this
      // chunk (our "successful" write went into a dying socket's buffer).
      // Counting it again would underflow the comm's inflight slot.
      ChunkTask d;
      while (w->tasks.TryPop(&d)) {
      }
      return;
    }
    if (c->lanes && c->lane_io) {
      uint64_t dt = MonotonicUs() - lane_t0;
      c->lane_io[w->idx].busy_us.fetch_add(dt ? dt : 1, std::memory_order_relaxed);
      c->lane_io[w->idx].bytes.fetch_add(t.len, std::memory_order_relaxed);
      Telemetry::Get().OnLaneBytes(true, w->idx, t.len);
    }
    Telemetry::Get().OnStreamBytes(true, w->idx, t.len,
                                   static_cast<int>(c->cls));
    Telemetry::Get().MaybeSampleStream(true, w->idx, w->fd);
    FinishChunk(w, t);
  }
}

void PumpCtrlUntilRetired(Comm* c, size_t idx);  // defined after frame handling

void RecvWorkerLoop(StreamWorker* w, bool spin) {
  Comm* c = w->comm;
  ChunkTask t;
  while (w->tasks.Pop(&t)) {
    t.state->MarkWireStart(MonotonicUs());
    FaultAction fa = FaultCheck(false, w->idx, w->fd, t.len);
    uint32_t wire_crc = 0;
    Status s = RecvChunkWire(w->fd, t.data, t.len, c->crc, spin, &wire_crc);
    if (!s.ok()) {
      if (ReceiverStreamFailed(c, w)) {
        // Become the ctrl pump: with the scheduler possibly parked waiting
        // for the NEXT message, nobody else may be reading the ctrl stream,
        // and the FAILOVER marker + retransmitted chunks arrive there.
        PumpCtrlUntilRetired(c, w->idx);
        return;
      }
      t.state->SetError(s.msg);
      FinishChunk(w, t);
      PoisonAndDrainQueue(c, s.msg);  // see SendWorkerLoop: settles orphans too
      continue;
    }
    if (fa == FaultAction::kCorrupt && t.len > 0) {
      t.data[t.len / 2] ^= 0x01;  // simulate wire damage before verification
    }
    if (c->crc && wire_crc != Crc32c(t.data, t.len)) {
      // Integrity failure is a REQUEST error, not a disconnect: the stream
      // framing is intact (we consumed exactly chunk+trailer), so the comm
      // keeps working for subsequent messages.
      Telemetry::Get().OnCrcError();
      t.state->SetError(ErrorKind::kCorruption,
                        "CRC32C mismatch on data stream " + std::to_string(w->idx) +
                            ": payload corrupted in transit");
    } else {
      Telemetry::Get().OnStreamBytes(false, w->idx, t.len,
                                     static_cast<int>(c->cls));
      if (c->lanes) Telemetry::Get().OnLaneBytes(false, w->idx, t.len);
      Telemetry::Get().MaybeSampleStream(false, w->idx, w->fd);
    }
    PopRec(c, w->idx, t.seq);
    FinishChunk(w, t);
  }
}

// Receiver-side: chunk a message and fan chunks out to stream workers
// round-robin from the rotating cursor. The send side runs the same chunk
// math + rotation inline in SendOneMsg (with ctrl-frame accounting on top),
// keeping the two chunk maps symmetric (SURVEY hard-part #2). Callers hold
// NO locks; the assignment happens under fo_mu.
void DispatchChunks(Comm* c, uint8_t* data, size_t len, const RequestPtr& state) {
  size_t csize = ChunkSize(len, c->min_chunksize, c->nstreams);
  size_t nchunks = ChunkCount(len, csize);
  state->total.store(nchunks, std::memory_order_release);  // 0-byte msg: done now
  if (nchunks == 0) {
    c->inflight.fetch_sub(1, std::memory_order_release);
    state->NotifyIfSettled();
    return;
  }
  state->NotifyIfSettled();
  MutexLock lk(c->fo_mu);
  size_t off = 0;
  for (size_t i = 0; i < nchunks; ++i) {
    size_t n = std::min(csize, len - off);
    AssignChunk(c, data + off, n, state);
    off += n;
  }
}

// Fail a message that never dispatched any chunk (its inflight slot is
// still held) and release the slot.
void FailMsg(Comm* c, const RequestPtr& state, const std::string& msg) {
  TPUNET_DBG("FailMsg: %s", msg.c_str());
  state->SetError(msg);
  state->total.store(0, std::memory_order_release);
  c->inflight.fetch_sub(1, std::memory_order_release);
  state->NotifyIfSettled();
}

// Poison the comm and promptly fail everything queued (reference broke its
// loop on ctrl error leaving queued requests to hang, nthread:396-401).
// Close() first so Pop drains without blocking — this runs on the CALLER
// thread via the inline fast path, not only on a dedicated scheduler that
// could afford to park as a fail-sink. Post-close isend/irecv see the
// closed queue (Push returns false) and fail their requests directly.
void PoisonAndDrainQueue(Comm* c, const std::string& why) {
  c->AbortStreams();
  c->msgs.Close();
  Msg m;
  while (c->msgs.Pop(&m)) {
    FailMsg(c, m.state, "comm broken by earlier ctrl-stream error: " + why);
  }
  // Orphaned failover records: chunks assigned to a dead-but-not-retired
  // stream have no worker task behind them (queues were drained when the
  // stream died), so nothing else will ever complete their accounting and
  // test() would hold the request forever waiting to quiesce.
  MutexLock lk(c->fo_mu);
  for (size_t i = 0; i < c->nstreams; ++i) {
    if (!c->stream_dead[i] || c->stream_retired[i]) continue;
    for (ChunkRec& r : c->recs[i]) {
      if (r.written) continue;  // already completion-counted by its worker
      r.state->SetError("comm poisoned with stream " + std::to_string(i) +
                        " mid-failover: " + why);
      AccountChunkDone(c, r.state, 0);
    }
    c->recs[i].clear();
    c->stream_retired[i] = 1;  // no retransmit is coming
  }
}

void FailAndDrain(Comm* c, const RequestPtr& state, const std::string& msg) {
  FailMsg(c, state, msg);
  PoisonAndDrainQueue(c, msg);
}

// ---- Lane adaptation (send side; docs/DESIGN.md "Lanes & adaptive
// striping") ----------------------------------------------------------------

// Weight resolution of the adaptive scheduler: the fastest lane is pinned
// at this weight and slower lanes scale below it, so byte shares track the
// measured rate ratio within one part in kLaneWeightScale.
constexpr uint32_t kLaneWeightScale = 16;

// Publish the comm's current weight vector as an epoch-stamped WEIGHTS ctrl
// frame. fo_mu held — the frame is totally ordered against LEN/FAILOVER
// frames, which is what confines re-striping to message boundaries.
Status PublishWeightsLocked(Comm* c) REQUIRES(c->fo_mu) {
  uint8_t buf[8 + 256];
  size_t n = BuildWeightsUnit(c->stripe_epoch, c->weights, buf);
  Status s = WriteAll(c->ctrl_fd, buf, n, c->spin);
  if (!s.ok()) return s;
  for (size_t i = 0; i < c->weights.size(); ++i) {
    Telemetry::Get().OnLaneWeight(i, c->weights[i]);
  }
  return Status::Ok();
}

// One adaptation tick, rate-limited to the comm's TPUNET_LANE_ADAPT_MS
// cadence: drain the per-lane service accounting into rate EWMAs, derive
// weight targets (rate-proportional, kLaneWeightScale resolution, floor 1),
// demote straggler-flagged lanes (TCP_INFO sRTT detector, rising-edge
// hysteresis upstream) by halving, and step current weights halfway toward
// their targets — geometric convergence whose half-life the fairness bench
// reads off the tpunet_lane_weight gauge. A changed vector bumps the epoch
// and publishes; an unchanged one costs two clock reads. The ctrl write is
// the only fallible step; the caller treats failure like a LEN-frame loss.
Status MaybeAdaptLanesLocked(Comm* c) REQUIRES(c->fo_mu) {
  if (!c->lanes || !c->is_send || !c->lane_adapt || !c->lane_io) return Status::Ok();
  uint64_t now = MonotonicUs();
  if (now < c->next_adapt_us) return Status::Ok();
  c->next_adapt_us = now + c->lane_adapt_us;
  uint64_t rmax = 0;
  bool moved = false;
  for (size_t i = 0; i < c->nstreams; ++i) {
    uint64_t bytes = c->lane_io[i].bytes.exchange(0, std::memory_order_relaxed);
    uint64_t busy = c->lane_io[i].busy_us.exchange(0, std::memory_order_relaxed);
    uint64_t ewma = c->lane_io[i].rate_ewma_bps.load(std::memory_order_relaxed);
    if (bytes > 0 && busy > 0) {
      uint64_t inst = bytes * 8 * 1000000 / busy;  // bits/s over service time
      ewma = ewma == 0 ? inst : (ewma + inst) / 2;
      c->lane_io[i].rate_ewma_bps.store(ewma, std::memory_order_relaxed);
      Telemetry::Get().OnLaneRate(i, ewma);
      moved = true;
    }
    // Re-export the weight gauge every tick (not only on publishes) so a
    // mid-run telemetry.reset() — how benches split warmup from
    // measurement — repopulates it without waiting for the next epoch.
    Telemetry::Get().OnLaneWeight(i, c->weights[i]);
    if (!c->stream_retired[i] && ewma > rmax) rmax = ewma;
  }
  if (!moved || rmax == 0) return Status::Ok();
  bool changed = false;
  for (size_t i = 0; i < c->nstreams; ++i) {
    if (c->stream_retired[i]) continue;
    uint64_t ewma = c->lane_io[i].rate_ewma_bps.load(std::memory_order_relaxed);
    uint32_t w = c->weights[i];
    uint32_t target = w;  // no measurement yet: hold
    if (ewma > 0) {
      target = static_cast<uint32_t>((kLaneWeightScale * ewma + rmax / 2) / rmax);
      if (target < 1) target = 1;
      if (target > kLaneWeightScale) target = kLaneWeightScale;
    }
    if (Telemetry::Get().StreamStraggling(true, i)) {
      uint32_t demoted = w > 1 ? w / 2 : 1;
      if (demoted < target) target = demoted;
    }
    uint32_t next = w;
    if (target > w) {
      next = w + std::max<uint32_t>(1, (target - w) / 2);
    } else if (target < w) {
      next = w - std::max<uint32_t>(1, (w - target) / 2);
    }
    if (next != w) {
      c->weights[i] = next;
      changed = true;
    }
  }
  if (!changed) return Status::Ok();
  c->stripe_epoch += 1;
  c->slots = BuildWrrSlots(c->weights);
  Telemetry::Get().OnRestripe();
  TPUNET_DBG("lane re-stripe epoch=%llu", (unsigned long long)c->stripe_epoch);
  return PublishWeightsLocked(c);
}

// Per-message sender work: chunk dispatch + ctrl length frame. Runs on the
// scheduler thread normally, or on the caller thread via the inline fast
// path (never concurrently — see Comm::inflight).
//
// Order matters on a shared core: the ctrl frame is the receiver's wakeup
// trigger (its ctrl read unblocks), and ctrl/data ride SEPARATE sockets, so
// nothing requires the frame to precede the payload bytes. Dispatching the
// chunks first means the receiver wakes to data already flowing instead of
// waking early, read-blocking on an empty data stream, and ping-ponging
// context switches with the sender's worker.
//
// The ctrl write is itself a completion unit (total = nchunks + 1): with
// chunks dispatched first, chunk completion alone no longer implies the
// frame is on the wire, and the inline fast path keys off "message fully
// settled" (inflight==0) to take the scheduler's role — if inflight could
// hit 0 with a scheduler ctrl write still pending, an inline frame could
// overtake it and desynchronize the receiver's ctrl stream.
bool SendOneMsg(Comm* c, const Msg& m) {
  uint8_t hdr[8];
  EncodeU64BE(m.len, hdr);
  size_t csize = ChunkSize(m.len, c->min_chunksize, c->nstreams);
  size_t nchunks = ChunkCount(m.len, csize);
  m.state->total.store(nchunks + 1, std::memory_order_release);
  Status s;
  bool dispatched = false;
  {
    // One fo_mu section covers this message's adaptation tick (possible
    // WEIGHTS frame), chunk assignment AND its ctrl length frame, so a
    // concurrent FAILOVER marker (NACK handler) lands strictly before or
    // strictly after the whole message in ctrl order — the receiver applies
    // the same assignment set either way, and a re-stripe can never split a
    // message.
    MutexLock lk(c->fo_mu);
    s = MaybeAdaptLanesLocked(c);
    if (s.ok()) {
      dispatched = true;
      size_t off = 0;
      for (size_t i = 0; i < nchunks; ++i) {
        size_t n = std::min(csize, m.len - off);
        AssignChunk(c, m.data + off, n, m.state);
        off += n;
      }
      s = WriteAll(c->ctrl_fd, hdr, sizeof(hdr), c->spin);
    }
  }
  if (!s.ok()) m.state->SetError(s.msg);
  if (!dispatched) {
    // WEIGHTS ctrl write failed before any chunk was assigned: the ctrl
    // unit below is the message's only completion unit, or test() would
    // wait forever for chunks that never dispatched.
    m.state->total.store(1, std::memory_order_release);
  }
  uint64_t total_units = dispatched ? nchunks + 1 : 1;
  uint64_t prior = m.state->completed.fetch_add(1, std::memory_order_acq_rel);
  if (prior + 1 >= total_units) {
    c->inflight.fetch_sub(1, std::memory_order_release);
  }
  m.state->NotifyIfSettled();
  if (!s.ok()) {
    PoisonAndDrainQueue(c, s.msg);
    return false;
  }
  return true;
}

void SendSchedulerLoop(Comm* c) {
  Msg m;
  while (c->msgs.Pop(&m)) {
    if (!SendOneMsg(c, m)) return;
  }
}

// ---- Receiver ctrl-frame vocabulary ---------------------------------------

// One ctrl frame, honoring a pump-stashed frame first. ctrl_mu held.
Status ReadCtrlFrameLocked(Comm* c, uint64_t* frame) REQUIRES(c->ctrl_mu) {
  if (c->has_pending_frame) {
    *frame = c->pending_frame;
    c->has_pending_frame = false;
    return Status::Ok();
  }
  uint8_t b[8];
  Status s = ReadExact(c->ctrl_fd, b, sizeof(b), c->spin);
  if (!s.ok()) return s;
  *frame = DecodeU64BE(b);
  return Status::Ok();
}

// FAILOVER marker: the sender retired stream k as of this point in ctrl
// order and retransmits every chunk the receiver's NACK declared missing —
// inline on the ctrl stream as [seq u64 | len u64 | payload | crc?] units.
// ctrl_mu held; takes fo_mu for the record/rotation update.
Status ProcessFailoverMarkerLocked(Comm* c, uint64_t frame) REQUIRES(c->ctrl_mu) {
  size_t k = (frame >> 48) & 0xff;
  uint64_t count = frame & 0xffffffffffffull;
  uint8_t b[16];
  Status s = ReadExact(c->ctrl_fd, b, 8, c->spin);
  if (!s.ok()) return s;
  uint64_t start_seq = DecodeU64BE(b);
  MutexLock lk(c->fo_mu);
  if (k >= c->nstreams || !c->stream_dead[k] || c->stream_retired[k]) {
    return Status::Inner("failover marker for stream " + std::to_string(k) +
                         " in an impossible state (protocol desync)");
  }
  if (start_seq != c->done_seq[k] || count != c->recs[k].size()) {
    return Status::Inner(
        "failover desync on stream " + std::to_string(k) + ": sender retransmits [" +
        std::to_string(start_seq) + ", +" + std::to_string(count) + "), receiver needs [" +
        std::to_string(c->done_seq[k]) + ", +" + std::to_string(c->recs[k].size()) + ")");
  }
  TPUNET_DBG("failover marker: stream %zu, %llu chunks over ctrl", k,
             (unsigned long long)count);
  for (ChunkRec& r : c->recs[k]) {
    s = ReadExact(c->ctrl_fd, b, sizeof(b), c->spin);
    if (!s.ok()) return s;
    uint64_t seq = DecodeU64BE(b);
    uint64_t len = DecodeU64BE(b + 8);
    if (seq != r.seq || len != r.len) {
      return Status::Inner("failover retransmit unit mismatch on stream " + std::to_string(k));
    }
    uint32_t wire_crc = 0;
    s = RecvChunkWire(c->ctrl_fd, r.data, r.len, c->crc, c->spin, &wire_crc);
    if (!s.ok()) return s;
    if (c->crc) {
      if (wire_crc != Crc32c(r.data, r.len)) {
        Telemetry::Get().OnCrcError();
        r.state->SetError(ErrorKind::kCorruption,
                          "CRC32C mismatch on failover retransmit (stream " +
                              std::to_string(k) + ")");
      }
    }
    if (!r.state->failed.load(std::memory_order_acquire)) {
      Telemetry::Get().OnStreamBytes(false, k, r.len, static_cast<int>(c->cls));
      if (c->lanes) Telemetry::Get().OnLaneBytes(false, k, r.len);
    }
    AccountChunkDone(c, r.state, r.len);
  }
  c->recs[k].clear();
  c->stream_retired[k] = 1;  // rotation excludes k from here on — both sides
  return Status::Ok();
}

// WEIGHTS epoch frame: the sender re-striped as of this point in ctrl
// order. Read the per-stream weight bytes, rebuild the slot table, and
// advance the epoch — subsequent LEN frames' messages are laid out on the
// new vector on both sides. ctrl_mu held; takes fo_mu for the table swap.
Status ProcessWeightsFrameLocked(Comm* c, uint64_t frame) REQUIRES(c->ctrl_mu) {
  uint64_t count = WeightsFrameCount(frame);
  uint64_t epoch = WeightsFrameEpoch(frame);
  if (!c->lanes || count != c->nstreams || count == 0) {
    return Status::Inner("WEIGHTS frame for " + std::to_string(count) +
                         " streams on a " + std::to_string(c->nstreams) +
                         "-stream " + (c->lanes ? "lane" : "non-lane") +
                         " comm (protocol desync)");
  }
  uint8_t wbytes[256];
  Status s = ReadExact(c->ctrl_fd, wbytes, count, c->spin);
  if (!s.ok()) return s;
  MutexLock lk(c->fo_mu);
  if (epoch <= c->stripe_epoch) {
    return Status::Inner("WEIGHTS epoch " + std::to_string(epoch) +
                         " is not past the current epoch " +
                         std::to_string(c->stripe_epoch) + " (protocol desync)");
  }
  for (uint64_t i = 0; i < count; ++i) {
    if (wbytes[i] == 0) {
      return Status::Inner("WEIGHTS frame carries a zero weight (protocol desync)");
    }
    c->weights[i] = wbytes[i];
    Telemetry::Get().OnLaneWeight(i, wbytes[i]);
  }
  bool initial = c->stripe_epoch == 0;
  c->stripe_epoch = epoch;
  c->slots = BuildWrrSlots(c->weights);
  // The epoch-1 frame is the sender's configured baseline, not a re-stripe.
  if (!initial) Telemetry::Get().OnRestripe();
  TPUNET_DBG("lane weights applied epoch=%llu", (unsigned long long)epoch);
  return Status::Ok();
}

// Per-message receiver ctrl-frame work; chunk handling differs between the
// scheduler path (dispatch to workers) and the lazy path (caller reads).
// Control frames (failover markers) encountered before the message's length
// frame are processed inline. The caller holds ctrl_mu (REQUIRES, checked
// by TSA) and MUST dispatch the message's chunk assignment before releasing
// it: a FAILOVER marker processed (by the pump) between this frame and the
// dispatch would retire a stream the sender still counted into THIS
// message's rotation, desynchronizing the chunk maps.
Status RecvCtrlFrame(Comm* c, const Msg& m, uint64_t* target) REQUIRES(c->ctrl_mu) {
  while (true) {
    uint64_t frame = 0;
    Status s = ReadCtrlFrameLocked(c, &frame);
    if (!s.ok()) return s;
    CtrlFrameView cf = DecodeCtrlFrame(frame);
    if (cf.kind == CtrlFrameKind::kFailover) {
      s = ProcessFailoverMarkerLocked(c, frame);
      if (!s.ok()) return s;
      continue;
    }
    if (cf.kind == CtrlFrameKind::kWeights) {
      s = ProcessWeightsFrameLocked(c, frame);
      if (!s.ok()) return s;
      continue;
    }
    if (cf.kind != CtrlFrameKind::kLen) {
      return Status::Inner("bogus ctrl frame 0x" + std::to_string(frame >> 56) +
                           "… — peer desynchronized");
    }
    *target = cf.len;
    if (*target > m.len) {
      // Peer sent more than the posted buffer — unrecoverable protocol
      // violation (the reference would panic slicing data[..target]).
      return Status::Inner("incoming message (" + std::to_string(*target) +
                           "B) exceeds posted recv buffer (" +
                           std::to_string(m.len) + "B)");
    }
    return Status::Ok();
  }
}

// Ctrl pump run by a failed receiver worker: until its stream's FAILOVER
// marker is processed (by this pump, the scheduler, or a lazy-recv caller —
// whoever owns ctrl_mu when the marker lands), keep the ctrl stream moving.
// A LEN frame read here is stashed for the real owner when its message is
// not yet posted — the pump never pairs frames with messages itself, which
// keeps frame↔message pairing strictly in pop order.
void PumpCtrlUntilRetired(Comm* c, size_t idx) {
  while (true) {
    {
      MutexLock lk(c->fo_mu);
      if (c->stream_retired[idx] || c->Aborted()) return;
    }
    if (!c->ctrl_mu.TryLock()) {
      // Someone else (scheduler / lazy caller) is reading ctrl; they will
      // process the marker. Check back shortly.
      std::this_thread::sleep_for(std::chrono::milliseconds(1));
      continue;
    }
    MutexLock lk(c->ctrl_mu, std::adopt_lock);
    if (c->has_pending_frame) {
      // A stashed LEN is waiting for its message; reading further frames
      // would reorder the stream. Yield until the scheduler consumes it.
      std::this_thread::sleep_for(std::chrono::milliseconds(1));
      continue;
    }
    struct pollfd pfd = {c->ctrl_fd, POLLIN, 0};
    int pr = ::poll(&pfd, 1, 20);
    if (pr < 0 && errno != EINTR) {
      PoisonAndDrainQueue(c, "ctrl poll failed during failover");
      return;
    }
    if (pr <= 0) continue;
    uint64_t frame = 0;
    Status s = ReadCtrlFrameLocked(c, &frame);
    if (!s.ok()) {
      PoisonAndDrainQueue(c, "ctrl stream lost during failover: " + s.msg);
      return;
    }
    CtrlFrameView cf = DecodeCtrlFrame(frame);
    if (cf.kind == CtrlFrameKind::kFailover) {
      s = ProcessFailoverMarkerLocked(c, frame);
      if (!s.ok()) {
        PoisonAndDrainQueue(c, s.msg);
        return;
      }
      continue;
    }
    if (cf.kind == CtrlFrameKind::kWeights) {
      s = ProcessWeightsFrameLocked(c, frame);
      if (!s.ok()) {
        PoisonAndDrainQueue(c, s.msg);
        return;
      }
      continue;
    }
    c->pending_frame = frame;  // LEN for a message the scheduler will pop
    c->has_pending_frame = true;
  }
}

// ---- Sender NACK reader ---------------------------------------------------

// Respond to a receiver NACK: mark the stream dead, emit the FAILOVER
// marker, and retransmit every record from the receiver's first missing seq
// over the ctrl stream. Returns false when the comm poisoned.
bool HandleNack(Comm* c, size_t k, uint64_t completed) {
  std::string poison;  // set on any verdict that must poison; applied after
                       // fo_mu is released (PoisonAndDrainQueue takes it)
  {
    MutexLock lk(c->fo_mu);
    if (c->Aborted()) return false;
    if (k >= c->nstreams || c->stream_retired[k]) {
      poison = "NACK for stream " + std::to_string(k) + " in impossible state";
    } else if (!c->stream_dead[k] && c->dead_count + 1 >= c->nstreams) {
      poison = "last data stream lost (NACK on stream " + std::to_string(k) + ")";
    }
    if (poison.empty()) {
      if (!c->stream_dead[k]) {
        c->stream_dead[k] = 1;
        c->dead_count += 1;
        Telemetry::Get().OnStreamFailover();
        // Unblock a worker mid-write on the dead conn; it sees stream_dead
        // and retires quietly.
        ::shutdown(c->workers[k]->fd, SHUT_RDWR);
        ChunkTask d;
        while (c->workers[k]->tasks.TryPop(&d)) {
        }
      }
      auto& q = c->recs[k];
      while (poison.empty() && !q.empty() && q.front().seq < completed) {
        if (!q.front().written) {
          poison = "failover desync: receiver claims a chunk never written";
          break;
        }
        q.pop_front();
      }
      if (poison.empty() && ((q.empty() && c->next_seq[k] != completed) ||
                             (!q.empty() && q.front().seq != completed))) {
        // The receiver still needs chunks whose records were pruned after
        // their message settled — the app may have freed those buffers, so
        // they are gone. Typed poison instead of a silent wrong answer.
        poison = "failover impossible on stream " + std::to_string(k) +
                 ": undelivered chunks were already released to the app "
                 "(kernel-buffered bytes lost with the connection)";
      }
      if (poison.empty()) {
        TPUNET_DBG("NACK stream %zu: retransmitting %zu chunks over ctrl", k, q.size());
        uint8_t b[16];
        EncodeU64BE(PackCtrlFrame(kCtrlFrameFailover, k, q.size()), b);
        EncodeU64BE(completed, b + 8);
        Status s = WriteAll(c->ctrl_fd, b, sizeof(b), c->spin);
        for (ChunkRec& r : q) {
          if (!s.ok()) break;
          EncodeU64BE(r.seq, b);
          EncodeU64BE(r.len, b + 8);
          // One writev per retransmit unit: [seq|len header, payload, crc?].
          uint8_t crcb[4];
          struct iovec iov[3] = {{b, sizeof(b)}, {r.data, r.len}, {crcb, 0}};
          int niov = 2;
          if (c->crc) {
            EncodeU32BE(Crc32c(r.data, r.len), crcb);
            iov[2].iov_len = sizeof(crcb);
            niov = 3;
          }
          s = WritevAll(c->ctrl_fd, iov, niov, c->spin);
          if (s.ok() && !r.written) {
            // First time these bytes reach the kernel: complete their
            // accounting (written records were counted by their worker).
            Telemetry::Get().OnStreamBytes(true, k, r.len,
                                           static_cast<int>(c->cls));
            if (c->lanes) Telemetry::Get().OnLaneBytes(true, k, r.len);
            AccountChunkDone(c, r.state, r.len);
            r.written = true;
          }
        }
        if (!s.ok()) {
          poison = "ctrl write failed during failover retransmit: " + s.msg;
        } else {
          q.clear();
          c->stream_retired[k] = 1;
        }
      }
    }
  }
  if (!poison.empty()) {
    PoisonAndDrainQueue(c, poison);
    return false;
  }
  return true;
}

// The reverse ctrl direction ended (the peer closed it, or died): no NACK
// will ever arrive. A stream that already failed over and awaits one holds
// records nothing else will account, so its request would never settle and
// wait() would sit on it for good (a SIGKILLed peer whose other stream's
// write still landed in the kernel buffer — tests/test_fault_paths.py under
// load): poison now. Streams that fail later see the flag and poison
// themselves (SenderStreamFailed). A clean close finds nothing orphaned.
void NackReaderGone(Comm* c, const char* why) {
  bool orphaned = false;
  {
    MutexLock lk(c->fo_mu);
    c->nack_reader_gone = true;
    for (size_t i = 0; i < c->nstreams; ++i) {
      orphaned |= c->stream_dead[i] && !c->stream_retired[i];
    }
  }
  if (orphaned) PoisonAndDrainQueue(c, why);
}

// Parked on the receiver→sender direction of the ctrl connection (silent in
// normal operation). Poll-based so spin mode's nonblocking ctrl fd does not
// busy-burn a core here.
void NackReaderLoop(Comm* c) {
  uint8_t buf[8];
  size_t got = 0;
  while (true) {
    struct pollfd pfd = {c->ctrl_fd, POLLIN, 0};
    int pr = ::poll(&pfd, 1, 100);
    if (c->Aborted()) return;
    if (pr < 0 && errno != EINTR) {
      return NackReaderGone(c, "reverse ctrl poll failed while awaiting NACK");
    }
    if (pr <= 0) continue;
    ssize_t n = ::recv(c->ctrl_fd, buf + got, sizeof(buf) - got, MSG_DONTWAIT);
    if (n == 0) {
      return NackReaderGone(c, "peer closed ctrl while a stream awaited its NACK");
    }
    if (n < 0) {
      if (errno == EINTR || errno == EAGAIN || errno == EWOULDBLOCK) continue;
      return NackReaderGone(c, "reverse ctrl read failed while awaiting NACK");
    }
    got += static_cast<size_t>(n);
    if (got < sizeof(buf)) continue;
    got = 0;
    uint64_t frame = DecodeU64BE(buf);
    if ((frame >> 56) != kCtrlFrameNack) {
      PoisonAndDrainQueue(c, "unexpected reverse ctrl frame from receiver");
      return;
    }
    if (!HandleNack(c, (frame >> 48) & 0xff, frame & 0xffffffffffffull)) return;
  }
}

void RecvSchedulerLoop(Comm* c) {
  Msg m;
  while (c->msgs.Pop(&m)) {
    uint64_t target = 0;
    c->ctrl_mu.Lock();
    Status s = RecvCtrlFrame(c, m, &target);
    if (!s.ok()) {
      c->ctrl_mu.Unlock();
      FailAndDrain(c, m.state, s.msg);
      return;
    }
    // NCCL semantics: recv buffer may exceed the message; true size comes
    // from the ctrl frame (reference nthread:507). Dispatched under the
    // SAME ctrl_mu hold as the frame read — see RecvCtrlFrame on why.
    DispatchChunks(c, m.data, static_cast<size_t>(target), m.state);
    c->ctrl_mu.Unlock();
  }
}

// Lazy-recv execution on the caller thread (from wait()): ctrl read + data
// read inline, no scheduler/worker hop and no completion wakeup. Only
// single-chunk-eligible messages park lazily (see irecv), so one ReadExact
// covers the payload. The owning worker thread is parked in Pop and never
// touches its fd without a task, so reading it here is exclusive.
void ExecuteLazyRecv(Comm* c, const Msg& m) {
  uint64_t target = 0;
  c->ctrl_mu.Lock();
  Status s = RecvCtrlFrame(c, m, &target);
  if (!s.ok()) {
    c->ctrl_mu.Unlock();
    FailMsg(c, m.state, s.msg);
    c->AbortStreams();
    return;
  }
  size_t len = static_cast<size_t>(target);
  size_t csize = ChunkSize(len, c->min_chunksize, c->nstreams);
  size_t nchunks = ChunkCount(len, csize);
  if (nchunks == 0) {
    c->ctrl_mu.Unlock();
    m.state->total.store(0, std::memory_order_release);
    c->inflight.fetch_sub(1, std::memory_order_release);
    m.state->NotifyIfSettled();
    return;
  }
  // nchunks == 1 by lazy eligibility. Assigned through the shared rotation
  // (failover bookkeeping stays symmetric with the sender) under the SAME
  // ctrl_mu hold as the frame read — see RecvCtrlFrame. The lock is
  // released before the blocking payload read: holding it there would
  // starve the ctrl pump this very chunk may depend on after a failover.
  m.state->total.store(nchunks, std::memory_order_release);
  size_t idx;
  uint64_t seq;
  bool dead;
  {
    MutexLock lk(c->fo_mu);
    idx = AssignStreamIdx(c);
    seq = c->next_seq[idx]++;
    c->recs[idx].push_back(ChunkRec{seq, m.data, len, m.state, false});
    dead = c->stream_dead[idx] != 0;
  }
  c->ctrl_mu.Unlock();
  if (!dead) {
    StreamWorker* w = c->workers[idx].get();
    m.state->MarkWireStart(MonotonicUs());
    uint32_t wire_crc = 0;
    Status rs = RecvChunkWire(w->fd, m.data, len, c->crc, c->spin, &wire_crc);
    if (rs.ok()) {
      if (c->crc && wire_crc != Crc32c(m.data, len)) {
        Telemetry::Get().OnCrcError();
        m.state->SetError(ErrorKind::kCorruption,
                          "CRC32C mismatch on data stream " + std::to_string(idx) +
                              ": payload corrupted in transit");
      } else {
        Telemetry::Get().OnStreamBytes(false, idx, len,
                                       static_cast<int>(c->cls));
        if (c->lanes) Telemetry::Get().OnLaneBytes(false, idx, len);
        Telemetry::Get().MaybeSampleStream(false, idx, w->fd);
      }
      PopRec(c, idx, seq);
      AccountChunkDone(c, m.state, len);
      return;
    }
    if (!ReceiverStreamFailed(c, c->workers[idx].get())) {
      m.state->SetError(rs.msg);
      AccountChunkDone(c, m.state, 0);
      PoisonAndDrainQueue(c, rs.msg);
      return;
    }
    // Fall through: the chunk arrives via the ctrl-stream retransmit.
  }
  // The assigned stream is dead: pump ctrl until the FAILOVER marker
  // delivers (and accounts) this chunk, or the comm poisons.
  PumpCtrlUntilRetired(c, idx);
}

// ---------------------------------------------------------------------------

class BasicEngine : public EngineBase, public BundleAdopter {
 public:
  BasicEngine()
      : spin_(GetEnvU64("TPUNET_SPIN", 0) != 0),
        inline_send_(GetEnvU64("TPUNET_INLINE_SEND", 1) != 0),
        lazy_recv_(GetEnvU64("TPUNET_LAZY_RECV", 1) != 0) {}

  ~BasicEngine() override {
    for (auto& c : send_comms_.DrainAll()) c->Shutdown();
    for (auto& c : recv_comms_.DrainAll()) c->Shutdown();
    // Wake any thread still parked in accept() — mirror of close_listen;
    // without this, destroying the engine would strand it forever.
    WakeAllListens();
  }

  Status connect(int32_t dev, const SocketHandle& handle, uint64_t* send_comm) override {
    Status sdev = CheckDev(dev);
    if (!sdev.ok()) return sdev;
    std::vector<int> data_fds;
    int ctrl_fd = -1;
    Status s = ConnectBundle(nics_, dev, handle, nstreams_, min_chunksize_, PreambleFlags(),
                             &data_fds, &ctrl_fd, lane_mode_ ? &lanes_ : nullptr);
    if (!s.ok()) return s;

    auto comm = std::make_shared<Comm>();
    comm->is_send = true;
    comm->nstreams = nstreams_;
    comm->min_chunksize = min_chunksize_;
    comm->spin = spin_;
    comm->crc = crc_;
    comm->cls = static_cast<TrafficClass>(traffic_class());
    comm->lanes = lane_mode_;
    comm->lane_adapt = lane_mode_ && lane_adapt_;
    comm->lane_adapt_us = lane_adapt_ms_ * 1000;
    comm->base_weights = LaneBaseWeights();
    comm->ctrl_fd = ctrl_fd;
    for (int fd : data_fds) {
      auto w = std::make_unique<StreamWorker>();
      w->fd = fd;
      w->idx = comm->workers.size();
      comm->workers.push_back(std::move(w));
    }
    if (spin_) {
      // Spin mode busy-polls nonblocking fds (set only after the blocking
      // preamble writes inside ConnectBundle). A failed fcntl must abort:
      // a silently-blocking fd would wedge the busy-poll path.
      Status ns = SetNonblocking(comm->ctrl_fd);
      for (auto& w : comm->workers) {
        if (ns.ok()) ns = SetNonblocking(w->fd);
      }
      if (!ns.ok()) {
        comm->Shutdown();
        return ns;
      }
    }
    s = StartThreads(comm.get());
    if (!s.ok()) {
      comm->Shutdown();
      return s;
    }
    uint64_t id = next_id_.fetch_add(1);
    send_comms_.Put(id, comm);
    *send_comm = id;
    return Status::Ok();
  }

  Status accept(uint64_t listen_comm, uint64_t* recv_comm) override {
    PartialBundle b;
    Status s = AcceptBundleOn(listen_comm, &b);
    if (!s.ok()) return s;
    return AdoptBundle(b, recv_comm);
  }

  // BundleAdopter seam (wire.h): the SHM engine fronts this engine on one
  // listen socket and hands non-SHM bundles back here.
  Status AdoptBundle(PartialBundle& b, uint64_t* recv_comm) override {
    if ((b.flags & kPreambleFlagShm) != 0) {
      // A zero-stream SHM hello reaching a plain TCP engine means the peer
      // runs TPUNET_SHM=1 and this process does not — wiring a zero-worker
      // comm would hang its first message, so fail loudly instead.
      b.CloseAll();
      return Status::Inner(
          "peer attempted shared-memory transport but TPUNET_SHM is not "
          "enabled here — set TPUNET_SHM identically on every rank");
    }
    return BuildRecvComm(b, recv_comm);
  }

  Status isend(uint64_t send_comm, const void* data, size_t nbytes, uint64_t* request) override {
    CommPtr c;
    if (!send_comms_.Get(send_comm, &c)) {
      return Status::Invalid("unknown send comm " + std::to_string(send_comm));
    }
    if (ForkGeneration() != c->fork_gen) {
      return Status::Inner("send comm created before fork(); its threads do not exist here");
    }
    // QoS admission control: a send over its class's in-flight byte budget
    // fails typed RIGHT HERE — nothing enqueued, nothing charged — so the
    // caller (serve router, trainer) gets retryable backpressure instead of
    // unbounded queue growth (docs/DESIGN.md "Transport QoS").
    uint64_t admitted = 0;
    Status as = QosScheduler::Get().AdmitMessage(c->cls, nbytes, &admitted);
    if (!as.ok()) return as;
    auto state = std::make_shared<RequestState>();
    state->qos_cls = static_cast<uint8_t>(c->cls);
    state->qos_admitted = admitted;
    state->t_post_us = MonotonicUs();
    ArmWatchdog(state, c);
    uint64_t id = next_id_.fetch_add(1);
    requests_.Put(id, state);
    Msg m{const_cast<uint8_t*>(static_cast<const uint8_t*>(data)), nbytes, state};
    // Inline fast path: on an idle comm the caller does the scheduler's
    // per-message work itself (8B ctrl write + chunk pushes, all
    // nonblocking-scale), skipping one thread hop per message. Data writes
    // stay on the workers — a blocking inline write could deadlock a
    // symmetric exchange once kernel socket buffers fill.
    if (c->inflight.fetch_add(1, std::memory_order_acq_rel) == 0 && inline_send_) {
      TPUNET_DBG("isend req=%llu len=%zu INLINE", (unsigned long long)id, nbytes);
      SendOneMsg(c.get(), m);
    } else {
      TPUNET_DBG("isend req=%llu len=%zu queued", (unsigned long long)id, nbytes);
      if (!c->msgs.Push(m)) FailMsg(c.get(), state, "send comm is poisoned");
    }
    *request = id;
    return Status::Ok();
  }

  Status irecv(uint64_t recv_comm, void* data, size_t nbytes, uint64_t* request) override {
    CommPtr c;
    if (!recv_comms_.Get(recv_comm, &c)) {
      return Status::Invalid("unknown recv comm " + std::to_string(recv_comm));
    }
    if (ForkGeneration() != c->fork_gen) {
      return Status::Inner("recv comm created before fork(); its threads do not exist here");
    }
    auto state = std::make_shared<RequestState>();
    state->t_post_us = MonotonicUs();
    ArmWatchdog(state, c);
    uint64_t id = next_id_.fetch_add(1);
    requests_.Put(id, state);
    Msg m{static_cast<uint8_t*>(data), nbytes, state};
    // A lazy recv already parked must hit the scheduler before this newer
    // message, or the ctrl frames would be consumed out of post order.
    UpgradeLazy(c.get());
    uint64_t prior = c->inflight.fetch_add(1, std::memory_order_acq_rel);
    size_t csize = ChunkSize(nbytes, c->min_chunksize, c->nstreams);
    bool single = ChunkCount(nbytes, csize) <= 1;
    TPUNET_DBG("irecv req=%llu len=%zu prior=%llu single=%d", (unsigned long long)id, nbytes, (unsigned long long)prior, (int)single);
    // Watchdog mode disables lazy parking: the lazy wait() path runs
    // BLOCKING ctrl/data reads on the caller thread, which the watchdog
    // (which lives in the condvar wait, WaitIn) could never interrupt —
    // bounded-wait guarantees beat the inline-hop optimization.
    if (prior == 0 && single && lazy_recv_ && watchdog_ms_ == 0) {
      // Park lazily: wait() executes the ctrl+data reads on the caller
      // thread (no scheduler/worker hop, no completion wakeup). test()
      // or a later irecv upgrades it onto the scheduler queue.
      // Single-chunk eligibility from the posted size is conservative:
      // the actual (<=posted) size can only have fewer chunks.
      MutexLock lk(c->lazy_mu);
      c->lazy_msg = m;
      c->has_lazy = true;
      c->lazy_req = id;
      g_lazy_parked.fetch_add(1, std::memory_order_relaxed);
      lazy_recv_owners_.Put(id, c);
    } else {
      if (!c->msgs.Push(m)) FailMsg(c.get(), state, "recv comm is poisoned");
    }
    *request = id;
    return Status::Ok();
  }

  Status test(uint64_t request, bool* done, size_t* nbytes) override {
    // Pollers (the NCCL shim) never call wait(), so a lazy recv would
    // starve: upgrade it onto the scheduler on the first poll. Match on the
    // request id — a stale owner entry (this request was already upgraded
    // elsewhere) must not kick a NEWER lazy parked on the same comm.
    CommPtr lc;
    if (lazy_recv_owners_.Take(request, &lc)) UpgradeLazyIf(lc.get(), request);
    RequestPtr state;
    if (!requests_.Get(request, &state)) {
      return Status::Invalid("unknown request " + std::to_string(request));
    }
    if (state->failed.load(std::memory_order_acquire)) {
      // Surface the error only once all dispatched chunk workers have
      // quiesced on this request — otherwise the caller could free/reuse the
      // buffer while a stream worker is still reading into it.
      if (!state->Done()) {
        *done = false;
        return Status::Ok();
      }
      state->ReleaseQosAdmission();  // consumption point: return budget bytes
      requests_.Erase(request);
      return Status{state->ErrKind(), "request failed: " + state->ErrorMsg()};
    }
    *done = state->Done();
    if (*done) {
      if (nbytes) *nbytes = state->nbytes.load(std::memory_order_acquire);
      RecordRequestStages(state);
      state->ReleaseQosAdmission();  // consumption point: return budget bytes
      requests_.Erase(request);  // reference leaked these (bagua_net.cc:111-121)
    }
    return Status::Ok();
  }

  Status wait(uint64_t request, size_t* nbytes) override {
    TPUNET_DBG("wait req=%llu enter", (unsigned long long)request);
    CommPtr c;
    if (lazy_recv_owners_.Take(request, &c)) {
      Msg m;
      bool mine = false;
      {
        MutexLock lk(c->lazy_mu);
        if (c->has_lazy && c->lazy_req == request) {
          m = c->lazy_msg;
          c->lazy_msg = Msg{};
          c->has_lazy = false;
          g_lazy_parked.fetch_sub(1, std::memory_order_relaxed);
          mine = true;
        }
      }
      if (mine) {
        // About to block in this comm's ctrl read: upgrade every OTHER
        // parked lazy first, or a multi-comm wait order could deadlock
        // against a lazy recv only this thread would have executed later.
        if (g_lazy_parked.load(std::memory_order_relaxed) != 0) {
          for (auto& lc : lazy_recv_owners_.DrainAll()) UpgradeLazy(lc.get());
        }
        ExecuteLazyRecv(c.get(), m);
      }
      Status st = WaitIn(requests_, request, nbytes);
      TPUNET_DBG("wait req=%llu lazy-exit ok=%d", (unsigned long long)request, (int)st.ok());
      return st;
    }
    // Non-lazy request: while it does not settle, keep upgrading every
    // parked lazy recv in this process. Without this, two ranks could both
    // park in a send-wait whose completion needs the peer's lazy recv to
    // run — a deadlock no caller ordering should be able to create. The
    // repeat (vs one-shot) covers a lazy parked by another thread after an
    // earlier pass; each pass is a no-op on an empty map.
    RequestPtr state;
    if (!requests_.Get(request, &state)) {
      return Status::Invalid("unknown request " + std::to_string(request));
    }
    int spins = 0;
    while (g_lazy_parked.load(std::memory_order_relaxed) != 0 &&
           !state->WaitSettledFor(50)) {
      // A lazy parked AFTER we fall through is its poster's own problem:
      // that thread's next wait/test upgrades it (every thread that parks
      // a lazy eventually waits something).
      for (auto& lc : lazy_recv_owners_.DrainAll()) UpgradeLazy(lc.get());
      if (++spins % 40 == 0) TPUNET_DBG("wait req=%llu still unsettled after %d spins (total=%llu completed=%llu failed=%d)", (unsigned long long)request, spins, (unsigned long long)state->total.load(), (unsigned long long)state->completed.load(), (int)state->failed.load());
    }
    Status st = WaitIn(requests_, request, nbytes);
    TPUNET_DBG("wait req=%llu exit ok=%d", (unsigned long long)request, (int)st.ok());
    return st;
  }

  Status close_send(uint64_t send_comm) override {
    CommPtr c;
    if (!send_comms_.Take(send_comm, &c)) {
      return Status::Invalid("unknown send comm " + std::to_string(send_comm));
    }
    c->Shutdown();
    return Status::Ok();
  }

  Status close_recv(uint64_t recv_comm) override {
    CommPtr c;
    if (!recv_comms_.Take(recv_comm, &c)) {
      return Status::Invalid("unknown recv comm " + std::to_string(recv_comm));
    }
    c->Shutdown();
    return Status::Ok();
  }

 private:
  // Progress-watchdog abort hook (only when TPUNET_PROGRESS_TIMEOUT_MS is
  // set): WaitIn's timeout verdict shuts the comm's sockets down so blocked
  // workers quiesce and the request surfaces its typed error. Weak capture —
  // the comm may be closed before the request is waited.
  void ArmWatchdog(const RequestPtr& state, const CommPtr& c) {
    if (watchdog_ms_ == 0) return;
    std::weak_ptr<Comm> wc = c;
    state->on_stall = [wc] {
      // Full poison, not just AbortStreams: records orphaned mid-failover
      // must settle too, or the verdict never surfaces through test().
      if (auto p = wc.lock()) {
        PoisonAndDrainQueue(p.get(), "progress watchdog verdict");
      }
    };
  }

  // Move a parked lazy recv onto the scheduler queue. The Push happens
  // UNDER lazy_mu: with it outside, a cross-thread upgrade could be
  // preempted between claim and push while the comm's caller posts (and
  // queues) a newer irecv, enqueueing the older recv after the newer one
  // and pairing ctrl frames with the wrong requests.
  static void UpgradeLazy(Comm* c) { UpgradeLazyIf(c, 0); }

  // expect_req != 0 restricts the upgrade to that specific parked request
  // (test()'s stale-entry guard); 0 upgrades whatever is parked.
  static void UpgradeLazyIf(Comm* c, uint64_t expect_req) {
    MutexLock lk(c->lazy_mu);
    if (!c->has_lazy) return;
    if (expect_req != 0 && c->lazy_req != expect_req) return;
    Msg m = c->lazy_msg;
    c->lazy_msg = Msg{};
    c->has_lazy = false;
    g_lazy_parked.fetch_sub(1, std::memory_order_relaxed);
    if (!c->msgs.Push(m)) FailMsg(c, m.state, "recv comm is poisoned");
  }

  Status StartThreads(Comm* c) {
    {
      // Failover bookkeeping is per-stream; size it before any IO thread
      // runs. No concurrency yet — the lock exists for the TSA contract.
      MutexLock lk(c->fo_mu);
      c->stream_dead.assign(c->nstreams, 0);
      c->stream_retired.assign(c->nstreams, 0);
      c->recs.resize(c->nstreams);
      c->next_seq.assign(c->nstreams, 0);
      c->done_seq.assign(c->nstreams, 0);
      if (c->lanes) {
        // Lane mode: both sides start on equal weights (the receiver knows
        // nothing else yet); the sender publishes its configured base
        // vector as epoch 1 before any message, so the first LEN frame
        // already finds both sides on the same (possibly non-uniform) map.
        c->weights.assign(c->nstreams, 1);
        c->slots = BuildWrrSlots(c->weights);
        c->lane_io.reset(new Comm::LaneIo[c->nstreams]);
        if (c->is_send) {
          c->weights = c->base_weights;
          c->weights.resize(c->nstreams, 1);
          c->stripe_epoch = 1;
          c->slots = BuildWrrSlots(c->weights);
          Status ps = PublishWeightsLocked(c);
          if (!ps.ok()) return ps;
        }
      }
    }
    bool spin = c->spin;
    for (auto& w : c->workers) {
      StreamWorker* wp = w.get();
      wp->comm = c;
      w->thread = c->is_send ? std::thread(SendWorkerLoop, wp, spin)
                             : std::thread(RecvWorkerLoop, wp, spin);
    }
    c->scheduler = std::make_unique<std::thread>(
        c->is_send ? SendSchedulerLoop : RecvSchedulerLoop, c);
    if (c->is_send) {
      // Reverse-ctrl NACK reader: the receiver speaks only when one of its
      // data streams dies (single-stream failover, docs/DESIGN.md).
      c->nack_reader = std::make_unique<std::thread>(NackReaderLoop, c);
    }
    return Status::Ok();
  }

  Status BuildRecvComm(PartialBundle& b, uint64_t* recv_comm) {
    auto comm = std::make_shared<Comm>();
    comm->is_send = false;
    // Sender's chunk-map inputs win — carried in the preamble so both sides
    // always partition messages identically (SURVEY hard-part #2). The CRC
    // flag travels the same way: the receiver verifies iff the sender
    // appends trailers, regardless of the local TPUNET_CRC setting.
    comm->nstreams = b.nstreams;
    comm->min_chunksize = b.min_chunksize;
    comm->crc = (b.flags & kPreambleFlagCrc) != 0;
    // Lane capability travels the same way (sender-wins): the receiver
    // mirrors the weighted slot-table rotation and accepts WEIGHTS frames.
    comm->lanes = (b.flags & kPreambleFlagLanes) != 0;
    // The traffic class travels the same way: the receiver accounts this
    // comm's bytes under the SENDER's class nibble.
    comm->cls = static_cast<TrafficClass>(PreambleClassOf(b.flags));
    comm->spin = spin_;
    comm->ctrl_fd = b.ctrl_fd;
    b.ctrl_fd = -1;
    Status ns = Status::Ok();
    if (spin_) ns = SetNonblocking(comm->ctrl_fd);  // ctrl carries the length frame
    // Data streams ordered by stream id (reference: BTreeMap nthread:432).
    for (auto& kv : b.data_fds) {
      auto w = std::make_unique<StreamWorker>();
      w->fd = kv.second;
      w->idx = comm->workers.size();
      if (spin_ && ns.ok()) ns = SetNonblocking(w->fd);
      comm->workers.push_back(std::move(w));
    }
    b.data_fds.clear();
    if (!ns.ok()) {
      comm->Shutdown();
      return ns;
    }
    ns = StartThreads(comm.get());
    if (!ns.ok()) {
      comm->Shutdown();
      return ns;
    }
    uint64_t id = next_id_.fetch_add(1);
    recv_comms_.Put(id, comm);
    *recv_comm = id;
    return Status::Ok();
  }

  bool spin_;
  bool inline_send_;
  bool lazy_recv_;
  IdMap<CommPtr> send_comms_;
  IdMap<CommPtr> recv_comms_;
  IdMap<RequestPtr> requests_;
  // request id -> comm whose lazy slot holds that request. Entries are
  // claimed (Take) by exactly one of wait/test/drain; stale entries after
  // an irecv-triggered upgrade are benign (claimer finds has_lazy false).
  IdMap<CommPtr> lazy_recv_owners_;
};

}  // namespace

std::unique_ptr<Net> CreateBasicEngine() { return std::make_unique<BasicEngine>(); }

std::unique_ptr<Net> CreateEngine() {
  // Engine seam (reference: src/lib.rs:20-29 BAGUA_NET_IMPLEMENT
  // BASIC|TOKIO); ours is TPUNET_IMPLEMENT BASIC|EPOLL. Every engine goes
  // out wrapped in the telemetry decorator so metrics/tracing cannot
  // diverge between engines.
  std::string impl = GetEnv("TPUNET_IMPLEMENT", GetEnv("BAGUA_NET_IMPLEMENT", "BASIC"));
  // Chaos hook: TPUNET_FAULT_SPEC arms a deterministic fault for this
  // process (fault.h); runtime arming goes through tpunet_c_fault_inject().
  ArmFaultFromEnv();
  auto engine = impl == "EPOLL" ? CreateEpollEngine() : CreateBasicEngine();
  // Intra-host shared memory (TPUNET_SHM=1, docs/DESIGN.md "Intra-host
  // shared memory"): front the TCP engine with the SHM engine — same-host
  // peers get mmap'd ring segments, everything else passes through. Must be
  // set identically on every rank (like the engine choice itself).
  if (GetEnvU64("TPUNET_SHM", 0) != 0) {
    engine = CreateShmEngine(std::move(engine));
  }
  return WrapWithTelemetry(std::move(engine));
}

}  // namespace tpunet
