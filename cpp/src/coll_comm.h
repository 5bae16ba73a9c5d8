// Internal declaration of the tpunet communicator, shared by the schedule
// translation units (docs/DESIGN.md "Schedules & algorithm selection").
//
// The communicator owns TOPOLOGY — the wired comm resources:
//   * ring channels (send to (rank+1)%W, recv from (rank-1+W)%W; channel 0
//     from Init, extra channels for overlapping async tickets), and
//   * the lazily-wired pairwise mesh (one send + one recv comm per peer),
// plus the machinery every schedule shares: the chunked exchange pipeline,
// the wire codec fusion, scratch buffers, trace spans, and the async ticket
// workers. SCHEDULES are member functions spread over per-algorithm TUs:
//   schedule_ring.cc — the chunk-pipelined ring (RS+AG AllReduce,
//     ReduceScatter, AllGather, pipelined Broadcast relay);
//   schedule_rhd.cc  — recursive halving-doubling AllReduce over the mesh
//     (2*log2(W') rounds; non-power-of-2 worlds fold the remainder in);
//   schedule_tree.cc — binomial tree (reduce-to-root + bcast AllReduce for
//     small payloads, binomial Broadcast).
// collectives.cc keeps lifecycle, wiring, dispatch and the async machinery.
// Which schedule runs is resolved per call by dispatch.h's selector.
#ifndef TPUNET_SRC_COLL_COMM_H_
#define TPUNET_SRC_COLL_COMM_H_

#include <algorithm>
#include <cstdint>
#include <deque>
#include <functional>
#include <map>
#include <string>
#include <thread>
#include <type_traits>
#include <vector>

#include "dispatch.h"
#include "flightrec.h"
#include "tpunet/bootstrap.h"
#include "tpunet/collectives.h"
#include "tpunet/mutex.h"
#include "tpunet/net.h"
#include "tpunet/qos.h"
#include "tpunet/telemetry.h"
#include "tpunet/utils.h"

namespace tpunet {
namespace internal {

// Broadcast store-and-forward granularity (ring relay AND binomial tree):
// per-chunk forwarding streams the payload instead of paying the full
// buffer's latency per hop.
constexpr size_t kBcastChunk = 1 << 20;

// Reduce-phase pipeline granularity: each ring step streams its slice in
// chunks this size so the reduction of chunk i overlaps the wire transfer of
// chunk i+1 (the NCCL pipelining insight — without it a step is strictly
// transfer-then-reduce and the reduce time adds to the critical path).
inline size_t RingChunkBytes() {
  static const size_t v = GetEnvU64("TPUNET_RING_CHUNKSIZE", 8 << 20);
  return v ? v : (8 << 20);
}

// Tag for the 8-byte hello a lazily-wired extra ring channel sends on its
// first message, distinguishing it from a pairwise-mesh hello (a bare rank,
// always < world) on the shared listener.
constexpr uint64_t kRingHelloTag = 0x52494E47ull << 32;  // "RING"

// Host-grouped topology view derived from the Init handshake's host ids —
// the shared input of the hierarchical schedules (schedule_hier.cc
// AllReduce, schedule_a2a.cc AllToAll). Hosts are ordered by their lowest
// rank; ranks within a host ascend — every rank derives the IDENTICAL
// grouping from the identical host_ids_ vector, which is what lets the
// stages pair up without any extra negotiation.
struct HierTopo {
  std::vector<std::vector<int>> hosts;  // per host, ascending ranks
  std::vector<int> local;  // ranks on my host, ascending (== hosts[hi])
  std::vector<int> inter;  // rank with my local index on each host (uniform only)
  size_t li = 0;           // my index in `local`
  size_t hi = 0;           // my host's index in `hosts`
  size_t R = 0, H = 0;
  bool uniform = false;    // every host carries the same rank count R
};
HierTopo BuildHierTopo(int rank, const std::vector<uint64_t>& ids);

// Public DType/RedOp enums -> the wire-layer ones the reduce kernels use.
inline WireDType ToWireDType(DType d) {
  switch (d) {
    case DType::kF32:
      return WireDType::kF32;
    case DType::kF64:
      return WireDType::kF64;
    case DType::kBF16:
      return WireDType::kBF16;
    case DType::kI32:
      return WireDType::kI32;
    case DType::kI64:
      return WireDType::kI64;
    case DType::kU8:
      return WireDType::kU8;
  }
  return WireDType::kU8;
}

inline WireRedOp ToWireRedOp(RedOp op) {
  switch (op) {
    case RedOp::kSum:
      return WireRedOp::kSum;
    case RedOp::kProd:
      return WireRedOp::kProd;
    case RedOp::kMin:
      return WireRedOp::kMin;
    case RedOp::kMax:
      return WireRedOp::kMax;
  }
  return WireRedOp::kSum;
}

// RAII trace span around one collective phase. Every rank runs the same
// collective program, so (comm_id, coll_seq, phase) names the SAME logical
// phase on every rank — the cross-rank join key telemetry.merge_traces()
// aligns per-rank trace files with. Zero cost when tracing is off (the
// caller passes tracing_enabled() as `on`; no string is built either way
// until the destructor fires with on=true) beyond the always-on flight-
// recorder enter/exit events — the ENTER event is what lets the postmortem
// name a phase nobody ever left (a hung rank never runs the destructor).
//
// A traced span is also the thread's innermost phase (Current()) while it
// lives: the choke points every schedule goes through — the request posts
// and waits of ScheduledCommunicator and the reduce wrappers below — split
// its time into part spans (docs/DESIGN.md 6c): coll.wait_peer (a recv
// blocked before the peer's first bytes of that message arrived),
// coll.wait_wire (a recv blocked after them, a send blocked on its
// completion, a post) and coll.reduce. With tracing off Current() is null
// and the choke points pay one branch.
class PhaseSpan {
 public:
  PhaseSpan(bool on, uint64_t comm_id, uint64_t seq, const char* kind, int step,
            uint64_t nbytes)
      : on_(on), comm_id_(comm_id), seq_(seq), kind_(kind), step_(step),
        nbytes_(nbytes), start_us_(on ? MonotonicUs() : 0),
        outer_(on ? current_ : nullptr) {
    flightrec::Record(flightrec::Ev::kPhaseEnter, comm_id_, seq_, nbytes_,
                      static_cast<uint32_t>(step_ < 0 ? 0 : step_), kind_);
    if (on_) current_ = this;
  }
  ~PhaseSpan() {
    flightrec::Record(flightrec::Ev::kPhaseExit, comm_id_, seq_, nbytes_,
                      static_cast<uint32_t>(step_ < 0 ? 0 : step_), kind_);
    if (!on_) return;
    current_ = outer_;
    Telemetry::Get().OnCollPhase(comm_id_, seq_, Phase().c_str(), start_us_,
                                 MonotonicUs() - start_us_, nbytes_);
  }
  PhaseSpan(const PhaseSpan&) = delete;
  PhaseSpan& operator=(const PhaseSpan&) = delete;

  // The innermost traced phase open on this thread, or nullptr.
  static PhaseSpan* Current() { return current_; }

  // One part of this phase, [start_us, end_us) on MonotonicUs()'s clock;
  // an empty interval writes nothing. `dir` is "recv", "send" or nullptr.
  void Part(const char* name, uint64_t start_us, uint64_t end_us,
            const char* dir) const {
    if (end_us <= start_us) return;
    Telemetry::Get().OnCollPart(name, comm_id_, seq_, Phase().c_str(), dir,
                                start_us, end_us - start_us);
  }

  // A request wait of [t0, t1): a recv's is the peer's until its first
  // bytes arrived (`first_wire_us`; 0 = never stamped, e.g. a zero-byte
  // message, whose whole wait is the peer's) and the wire's after; a
  // send's is the wire's.
  void Waited(uint64_t t0, uint64_t t1, bool recv, uint64_t first_wire_us) const {
    if (!recv) {
      Part("coll.wait_wire", t0, t1, "send");
      return;
    }
    uint64_t split = first_wire_us == 0 ? t1 : std::min(std::max(first_wire_us, t0), t1);
    Part("coll.wait_peer", t0, split, nullptr);
    Part("coll.wait_wire", split, t1, "recv");
  }

 private:
  std::string Phase() const {
    return step_ < 0 ? std::string(kind_) : std::string(kind_) + "." + std::to_string(step_);
  }

  static inline thread_local PhaseSpan* current_ = nullptr;
  bool on_;
  uint64_t comm_id_, seq_;
  const char* kind_;
  int step_;
  uint64_t nbytes_;
  uint64_t start_us_;
  PhaseSpan* outer_;
};

// Runs `fn` as a part `name` of the thread's innermost traced phase (a bare
// call when there is none) and returns what it returns.
template <class F>
inline auto InPart(const char* name, const char* dir, F&& fn) {
  const PhaseSpan* ph = PhaseSpan::Current();
  if (ph == nullptr) return fn();
  const uint64_t t0 = MonotonicUs();
  if constexpr (std::is_void_v<decltype(fn())>) {
    fn();
    ph->Part(name, t0, MonotonicUs(), dir);
  } else {
    auto out = fn();
    ph->Part(name, t0, MonotonicUs(), dir);
    return out;
  }
}

// The 3-operand reduction kernels (dst[i] = a[i] op b[i]) live in utils.cc
// as ReduceInto — SIMD with runtime dispatch, fork-join pool, and the
// tpunet_reduce_bytes_total counter. Schedules reduce through these
// wrappers (the codec's fused decode+reduce too), never the kernels
// directly, so every reduce is a part of its phase.
inline void Reduce(void* dst, const void* a, const void* b, size_t n,
                   DType dtype, RedOp op) {
  InPart("coll.reduce", nullptr,
         [&] { ReduceInto(dst, a, b, n, ToWireDType(dtype), ToWireRedOp(op)); });
}

inline void DecodeReduce(WireCodec c, float* dst, const float* local,
                         const uint8_t* wire, size_t n, WireRedOp op) {
  InPart("coll.reduce", nullptr, [&] { CodecDecodeReduce(c, dst, local, wire, n, op); });
}

inline void DecodeReduceQuantize(WireCodec c, float* dst, const float* local,
                                 const uint8_t* wire, uint8_t* enc_out, size_t n,
                                 WireRedOp op) {
  InPart("coll.reduce", nullptr,
         [&] { CodecDecodeReduceQuantize(c, dst, local, wire, enc_out, n, op); });
}

class ScheduledCommunicator : public Communicator {
 public:
  // A channel is one independent ring: a send comm to (rank+1)%W and a recv
  // comm from (rank-1+W)%W, plus the scratch its pipelined reduce uses.
  // Channel 0 is wired at Init and carries every blocking collective; extra
  // channels exist so concurrent async tickets can overlap on the wire
  // (ticket k+1's transfer no longer waits for ticket k's reduce).
  struct RingChannel {
    uint64_t send_comm = 0;
    uint64_t recv_comm = 0;
    ScratchBuf scratch;  // chunk landing slots; aligned, never zero-filled
    // recv_comm said once that it cannot reduce as it lands
    // (Net::irecv_reduce): ExchangeReduce no longer asks.
    bool recv_copies_only = false;
  };

  ScheduledCommunicator(int rank, int world, WireCodec codec, CollAlgo algo,
                        TrafficClass cls)
      : rank_(rank), world_(world), codec_(codec), algo_override_(algo),
        cls_(cls) {}
  ~ScheduledCommunicator() override;

  Status Init(const std::string& coordinator);

  // -- Communicator interface (collectives.cc unless noted) -----------------
  Status AllReduce(const void* sendbuf, void* recvbuf, size_t count, DType dtype,
                   RedOp op) override;
  Status ReduceScatter(const void* sendbuf, void* recvbuf, size_t recv_count,
                       DType dtype, RedOp op) override;  // schedule_ring.cc
  Status AllGather(const void* sendbuf, void* recvbuf, size_t bytes_per_rank)
      override;  // schedule_ring.cc
  Status Broadcast(void* buf, size_t nbytes, int root) override;
  Status AllToAll(const void* sendbuf, void* recvbuf, size_t bytes_per_rank) override;
  Status AllToAllTyped(const void* sendbuf, void* recvbuf, size_t count_per_rank,
                       DType dtype) override;
  Status NeighborExchange(const void* sendbuf, size_t send_nbytes, void* recvbuf,
                          size_t recv_nbytes, size_t* got) override;
  Status Barrier() override;
  Status IAllReduce(const void* sendbuf, void* recvbuf, size_t count, DType dtype,
                    RedOp op, uint64_t* ticket) override;
  Status IAllToAll(const void* sendbuf, void* recvbuf, size_t bytes_per_rank,
                   uint64_t* ticket) override;
  Status WaitTicket(uint64_t ticket) override;
  Status TestTicket(uint64_t ticket, bool* done) override;
  int rank() const override { return rank_; }
  int world_size() const override { return world_; }
  int32_t wire_codec() const override { return static_cast<int32_t>(codec_); }

 private:
  // -- dispatch (collectives.cc) --------------------------------------------
  // Resolve the schedule for an AllReduce/Broadcast of `nbytes` payload and
  // bump tpunet_coll_algo_selected_total. Deterministic from negotiated
  // state, so every rank resolves identically.
  CollAlgo ResolveAlgo(CollKind coll, uint64_t nbytes);
  // Run one AllReduce under the already-resolved schedule (the async ticket
  // job body; blocking calls go through the ticket path or call it inline).
  Status DoAllReduce(const void* sendbuf, void* recvbuf, size_t count, DType dtype,
                     RedOp op, RingChannel& ch, uint64_t seq, CollAlgo algo);

  // -- ring schedule (schedule_ring.cc) -------------------------------------
  Status DoAllReduceRing(const void* sendbuf, void* recvbuf, size_t count,
                         DType dtype, RedOp op, RingChannel& ch, uint64_t seq);
  Status DoBroadcastRing(void* buf, size_t nbytes, int root, uint64_t seq);
  // One pipelined reduce ring step — see schedule_ring.cc for the contract.
  Status ExchangeReduce(const uint8_t* sendbuf, size_t send_nbytes, uint8_t* accum,
                        size_t recv_nbytes, DType dtype, RedOp op, RingChannel& ch,
                        const uint8_t* local = nullptr);
  Status ExchangeReduceCodec(const uint8_t* sendbuf, size_t send_nbytes,
                             uint8_t* accum, size_t recv_nbytes, RedOp op,
                             RingChannel& ch, const uint8_t* local,
                             uint8_t* fused_enc = nullptr, size_t scratch_off = 0);
  Status AgPhaseCodec(float* data, size_t count, RingChannel& ch, uint64_t seq,
                      bool tracing);
  // One ring step: recv from prev into recvbuf while sending sendbuf to next.
  Status Exchange(const void* sendbuf, size_t send_nbytes, void* recvbuf,
                  size_t recv_nbytes, size_t* got, RingChannel& ch);
  Status ExchangePosted(uint64_t rreq, const void* sendbuf, size_t send_nbytes,
                        size_t recv_nbytes, size_t* got, RingChannel& ch);
  Status DrainSends(std::vector<uint64_t>& reqs, Status primary);
  size_t CodecChunkElems() const;

  // -- halving-doubling schedule (schedule_rhd.cc) --------------------------
  Status DoAllReduceRhd(const void* sendbuf, void* recvbuf, size_t count,
                        DType dtype, RedOp op, uint64_t seq);
  // Full-duplex pairwise step on the mesh comms of `peer`; zero-length
  // directions are skipped (empty halving segments at tiny counts) — both
  // sides derive sizes from the same geometry, so the skips pair up.
  Status MeshExchange(int peer, const void* sendbuf, size_t send_nbytes,
                      void* recvbuf, size_t recv_nbytes);
  Status MeshSend(int peer, const void* buf, size_t nbytes);
  Status MeshRecv(int peer, void* buf, size_t nbytes);

  // -- binomial tree schedule (schedule_tree.cc) ----------------------------
  Status DoAllReduceTree(const void* sendbuf, void* recvbuf, size_t count,
                         DType dtype, RedOp op, uint64_t seq);
  Status DoBroadcastTree(void* buf, size_t nbytes, int root, uint64_t seq);

  // -- hierarchical two-level schedule (schedule_hier.cc) -------------------
  // Intra-host ReduceScatter (local ring over the mesh, SHM when
  // TPUNET_SHM=1) -> one-rank-per-host inter-host AllReduce of each local
  // rank's owned shard (ring or rhd among the H same-local-index ranks,
  // picked through the dispatch table) -> intra-host AllGather. Per-rank
  // DCN wire bytes drop to 2*(S/R)*(H-1)/H. Requires a usable hierarchy
  // (>= 2 hosts, uniform R ranks/host — host_ids_ from the Init blob).
  bool HierUsable() const;
  bool HierProfitable() const;  // usable AND R >= 2 (auto-upgrade gate)
  Status DoAllReduceHier(const void* sendbuf, void* recvbuf, size_t count,
                         DType dtype, RedOp op, uint64_t seq);
  // Ring step with DIFFERENT send/recv peers (ring RS/AG inside a rank
  // subgroup rides the pairwise mesh): irecv from `from`, isend to `to`,
  // wait both even on error. Zero-length directions skip (geometry is
  // identical on both sides, so the skips pair).
  Status MeshShift(int to, const void* sendbuf, size_t send_nbytes, int from,
                   void* recvbuf, size_t recv_nbytes);
  // AllReduce over an ordered rank subgroup (group[idx] == rank_) operating
  // in place on `data`; wire rounds counted under hier.intra/hier.inter via
  // `inter`. f32 payloads honor the negotiated codec on the INTER stage
  // (encoded atoms forward verbatim in the AG half, so every group member
  // materializes bit-identical bytes); intra stages ship raw bytes — the
  // whole point of the hierarchy is that those hops are memory-cheap.
  Status SubgroupAllReduce(const std::vector<int>& group, size_t idx,
                           uint8_t* data, size_t count, DType dtype, RedOp op,
                           bool inter, uint64_t seq);
  // Recursive halving-doubling flavor of the above (2*log2(G) rounds) for
  // power-of-two subgroups on uncompressed payloads; the dispatch table's
  // rhd verdict for (shard size, H) routes here. Codec payloads stay on the
  // subgroup ring — its verbatim-forwarding AG is where the cross-rank
  // bit-identity machinery lives.
  Status SubgroupRhdAllReduce(const std::vector<int>& group, size_t idx,
                              uint8_t* data, size_t count, DType dtype,
                              RedOp op, uint64_t seq);

  // -- AllToAll dispatch + flat paths (collectives.cc) ----------------------
  // Resolve the AllToAll schedule for one call: TPUNET_A2A_ALGO override
  // (negotiated at Init) > dispatch table (coll="alltoall") > built-in
  // pairwise, with ApplyHierPolicy upgrading to the two-stage transpose on
  // a profitable topology and the mesh-budget guard routing oversized
  // worlds to the ring relay. Bumps tpunet_coll_algo_selected_total.
  CollAlgo ResolveA2aAlgo(uint64_t bytes_per_rank);
  // Run one byte-oriented AllToAll under the already-resolved schedule
  // (shared by the blocking call, the async ticket job, and the typed
  // wrapper). `ch` carries the ring-relay variant; pairwise/hier ride the
  // mesh. Every flat wire byte lands in tpunet_a2a_bytes_total{stage="flat"}
  // (the hier stages count inside schedule_a2a.cc).
  Status DoAllToAll(const uint8_t* in, uint8_t* out, size_t B, uint64_t seq,
                    CollAlgo algo, RingChannel& ch);
  Status PairwiseAllToAll(const uint8_t* in, uint8_t* out, size_t B);

  // -- hierarchical AllToAll (schedule_a2a.cc) ------------------------------
  // Two-stage transpose over the mesh (docs/DESIGN.md "Hierarchical
  // AllToAll"): R-1 intra-host regroup rounds (H·B bytes each, SHM under
  // TPUNET_SHM=1) land every block destined to a local-index-li rank on
  // this rank, then H-1 inter-host column rounds (R·B bytes each, the only
  // DCN hops) complete the exchange. Requires a usable hierarchy.
  Status DoAllToAllHier(const uint8_t* in, uint8_t* out, size_t B, uint64_t seq);

  // -- wiring / lifecycle (collectives.cc) ----------------------------------
  Status ConnectAndWire(const SocketHandle& next_handle);
  Status AcceptHello(uint64_t* rc, uint64_t* hello);
  Status ConnectHello(int peer, uint64_t hello, uint64_t* comm);
  Status EnsureMesh();
  // EnsureMesh plus a one-time ring-step quiesce OVER THE MESH COMMS: no
  // rank proceeds past the first mesh use until EVERY rank finished wiring,
  // so a later listener-touching op (EnsureAsyncChannels on a fast rank)
  // can never be mistaken for a mesh connect by a peer still in its accept
  // loop. Riding the mesh (not channel 0) keeps mesh-queue jobs disjoint
  // from ring-channel traffic — what lets async mesh tickets overlap ring
  // tickets.
  Status EnsureMeshQuiesced();
  Status EnsureAsyncChannels(size_t nch);
  static size_t AsyncChannelCount();

  // -- async worker machinery (collectives.cc) ------------------------------
  bool TicketLive(uint64_t ticket) REQUIRES(async_mu_);
  // First async submission: wire the extra ring channels and spawn one
  // worker per queue (ring queues 0..C-1 plus the dedicated mesh queue C).
  Status EnsureAsyncWorkers() REQUIRES(async_mu_);
  // Queue index of the dedicated mesh worker — the serialization domain of
  // every mesh-comm job (rhd/tree/hier/a2a share the one pairwise mesh, so
  // they must run one at a time and in submission order), kept OFF the ring
  // channels so a mesh ticket can overlap ring tickets on disjoint comms.
  size_t MeshQueueIndex() REQUIRES(async_mu_) { return queues_.size() - 1; }
  void AsyncWorkerLoop(size_t ch);
  bool AsyncIdle() REQUIRES(async_mu_);
  void FenceAsync();
  void StopAsyncWorker();

  // Blocking condvar waits — a test() poll loop here competes with the
  // stream worker threads for CPU (catastrophic on few-core hosts). Under
  // a traced phase each wait is a part of it (PhaseSpan::Waited); the
  // recv's split point is the first-wire stamp the engine hands the
  // consuming thread (Telemetry::TakeConsumedFirstWireUs).
  //
  // The posts are parts too: an engine's caller-thread fast path (epoll's
  // TryInline) moves bytes inside isend/irecv, so their time is the
  // wire's (coll.wait_wire) like a wait after the first bytes.
  Status PostRecv(uint64_t comm, void* buf, size_t nbytes, uint64_t* req) {
    return InPart("coll.wait_wire", "recv",
                  [&] { return net_->irecv(comm, buf, nbytes, req); });
  }
  Status PostSend(uint64_t comm, const void* buf, size_t nbytes, uint64_t* req) {
    return InPart("coll.wait_wire", "send",
                  [&] { return net_->isend(comm, buf, nbytes, req); });
  }
  // A recv that reduces as it lands (accum = local op incoming); an error,
  // with nothing posted, where the comm cannot (Net::irecv_reduce).
  Status PostRecvReduce(uint64_t comm, uint8_t* accum, const uint8_t* local,
                        size_t nbytes, DType dtype, RedOp op, uint64_t* req) {
    return InPart("coll.wait_wire", "recv", [&] {
      return net_->irecv_reduce(comm, accum, local, nbytes, ToWireDType(dtype),
                                ToWireRedOp(op), req);
    });
  }
  Status WaitRecv(uint64_t req, size_t* nbytes) { return Wait(req, nbytes, true); }
  Status WaitSend(uint64_t req) { return Wait(req, nullptr, false); }
  Status Wait(uint64_t req, size_t* nbytes, bool recv) {
    const PhaseSpan* ph = PhaseSpan::Current();
    if (ph == nullptr) return net_->wait(req, nbytes);
    const uint64_t t0 = MonotonicUs();
    Telemetry::TakeConsumedFirstWireUs();  // drop a stale hand-off
    Status st = net_->wait(req, nbytes);
    ph->Waited(t0, MonotonicUs(), recv, Telemetry::TakeConsumedFirstWireUs());
    return st;
  }

  // The codec engages only where elements are KNOWN f32: AllReduce /
  // ReduceScatter payloads and the AG phase inside AllReduce. The
  // byte-oriented collectives (AllGather, Broadcast, AllToAll,
  // NeighborExchange, Barrier) carry opaque bytes — rendezvous handles,
  // tokens, arbitrary dtypes — and are never lossily compressed
  // (docs/DESIGN.md "Compressed collectives").
  bool UseCodec(DType dtype) const {
    return codec_ != WireCodec::kF32 && dtype == DType::kF32 && world_ > 1;
  }

  int rank_;
  int world_;
  // Wire compression codec for f32 collectives, fixed at construction and
  // verified equal across ranks by the Init handshake (UseCodec above).
  WireCodec codec_ = WireCodec::kF32;
  // Per-communicator schedule override (kAuto = per-size selection) and the
  // dispatch table loaded from TPUNET_DISPATCH_TABLE. Both are negotiated
  // at Init — (override, table CRC) ride the codec handshake — so every
  // rank resolves the same schedule for the same collective.
  CollAlgo algo_override_ = CollAlgo::kAuto;
  // AllToAll schedule override (TPUNET_A2A_ALGO; the legacy TPUNET_A2A=ring
  // spelling folds in as a kRing override). Negotiated at Init — the byte
  // rides the same handshake blob — because half a world on the pairwise
  // mesh and half on the two-stage transpose deadlocks, it never corrupts.
  CollAlgo a2a_override_ = CollAlgo::kAuto;
  // QoS traffic class for every comm this communicator wires (latency for
  // serving P2P links, bulk for gradient rings, control for bootstrap-ish
  // traffic). Negotiated at Init — the class byte rides the codec/algo
  // handshake — so the whole group schedules under one class.
  TrafficClass cls_ = TrafficClass::kBulk;
  DispatchTable dispatch_;
  std::unique_ptr<Net> net_;
  std::unique_ptr<Bootstrap> bootstrap_;
  uint64_t listen_comm_ = 0;
  // Collective tracing identity: comm_id hashes (coordinator, world) — the
  // same on every rank — and coll_seq_ counts collectives in program order
  // (MPI semantics make the program identical across ranks), so
  // (trace_comm_id_, coll_seq_, phase) tags match rank-to-rank.
  uint64_t trace_comm_id_ = 0;
  uint64_t coll_seq_ = 0;
  // channels_[0] is the Init-wired ring every blocking collective uses;
  // channels_[1..] are wired by EnsureAsyncChannels for overlapping async
  // tickets. Stable after the first IAllReduce (workers capture indices).
  std::vector<RingChannel> channels_;
  // Scratch buffers reused across calls; a Communicator is not thread-safe
  // (one collective at a time, like an MPI communicator).
  // Pairwise-mesh comms for AllToAll and the rhd/tree schedules, keyed by
  // peer rank (0 = unwired / self). Wired lazily by EnsureMesh from
  // all_handles_; mesh_quiesced_ records the one-time wiring barrier.
  std::vector<SocketHandle> all_handles_;
  // Per-rank host ids from the Init handshake blob (utils.h HostId()) —
  // the topology input of the hierarchical schedule. Size world_ (a
  // single-rank world holds just its own id).
  std::vector<uint64_t> host_ids_;
  std::vector<uint64_t> mesh_send_;
  std::vector<uint64_t> mesh_recv_;
  bool mesh_quiesced_ = false;
  ScratchBuf work_;
  std::vector<uint8_t> barrier_scratch_;
  ScratchBuf a2a_fwd_, a2a_rcv_;
  // Hierarchical-AllToAll staging: slot (j, h) holds the block from local
  // source j destined to host h's local-index-li rank (schedule_a2a.cc
  // layout), plus the typed wrapper's encoded in/out assemblies (scale
  // blocks restart per (src, dst) block — the bit-identity contract).
  ScratchBuf a2a_stage_, a2a_enc_in_, a2a_enc_out_;
  // Mesh-schedule scratch (rhd halves / tree partials, and the encoded-atom
  // assembly the codec AG forwards verbatim). Non-ring jobs serialize on
  // channel 0's queue — or run on the fenced caller thread — so one set
  // suffices; never touched by two threads at once.
  ScratchBuf mesh_scratch_, mesh_enc_;
  // Async (nonblocking-collective) state; async_mu_ guards all of it. Worker
  // c is the only place async jobs touch channel c's comms/scratch, and
  // FenceAsync keeps the sync paths out while any job runs. async_mu_ is
  // released before any job executes, so it is never held around engine or
  // request locks (docs/DESIGN.md "Concurrency model").
  Mutex async_mu_;
  CondVar work_cv_, done_cv_;
  std::vector<std::deque<std::pair<uint64_t, std::function<Status()>>>> queues_
      GUARDED_BY(async_mu_);
  std::vector<uint64_t> running_ GUARDED_BY(async_mu_);
  std::map<uint64_t, Status> done_ GUARDED_BY(async_mu_);
  Status async_wire_status_ = Status::Ok();
  uint64_t next_ticket_ GUARDED_BY(async_mu_) = 1;
  bool worker_started_ GUARDED_BY(async_mu_) = false;
  bool stop_ GUARDED_BY(async_mu_) = false;
  // Joined in StopAsyncWorker AFTER async_mu_ is released (a worker must be
  // able to take the lock to observe stop_), so the vector itself cannot be
  // async_mu_-guarded; it only grows under the lock in IAllReduce.
  std::vector<std::thread> workers_;
};

}  // namespace internal
}  // namespace tpunet

#endif  // TPUNET_SRC_COLL_COMM_H_
