// tpunet observability: per-request tracing + transport metrics + deep
// per-stream TCP introspection.
//
// TPU-native re-design of the reference's OpenTelemetry stack (SURVEY §5;
// reference: nthread_per_socket_backend.rs:108-212): no third-party SDK,
// one in-process singleton the engines feed through a decorator.
//
// Tracing (reference: root span "BaguaNet-{rank}" nthread:132-137, child
// span per isend/irecv with id+nbytes attrs :529-538, ended at test()
// completion :606): spans are buffered and flushed as VALID Chrome-trace
// JSON (json.load-able, Perfetto-loadable) to
// TPUNET_TRACE_DIR/tpunet-trace-rank<R>.json. Env-gated like the reference
// (rank 0-7 AND the dir var set, nthread:108-130), or enabled at runtime via
// tpunet_c_trace_set_dir() / tpunet.telemetry.profile(). Besides request
// spans the file carries collective phase spans tagged
// (comm_id, coll_seq, phase) — the cross-rank join key merge_traces() uses
// to align per-rank files into one timeline — and straggler instant events.
//
// Metrics (reference: isend/irecv_nbytes histograms with boundaries
// [16,1024,4096,1048576] nthread:139-180, bytes/s observers :343-348,
// in-flight gauge tokio:184-190): counters are always-on atomics; a push
// thread PUTs Prometheus text to a pushgateway at TPUNET_METRICS_ADDR every
// TPUNET_METRICS_INTERVAL_MS (default 1000), and an on-demand scrape
// listener serves the same exposition at http://:TPUNET_METRICS_PORT/metrics.
//
// TCP introspection: a rate-limited getsockopt(TCP_INFO) sampler on the
// engines' data paths (TPUNET_TCPINFO_INTERVAL_MS per stream slot, default
// 100, 0 disables) exports per-stream RTT / retransmit / cwnd /
// delivery-rate gauges, a Jain's-fairness gauge over windowed per-stream
// bytes, and a straggler detector (smoothed RTT > k× the median across
// active streams -> tpunet_straggler_events_total + a trace instant event).
#ifndef TPUNET_TELEMETRY_H_
#define TPUNET_TELEMETRY_H_

#include <atomic>
#include <cstdint>
#include <memory>
#include <string>

#include "tpunet/net.h"

namespace tpunet {

// Histogram bucket upper bounds in bytes (reference: nthread:139-141), plus
// a +Inf bucket.
constexpr uint64_t kHistBounds[4] = {16, 1024, 4096, 1048576};
constexpr int kHistBuckets = 5;

// Stage-latency histogram bounds in microseconds (+Inf bucket appended):
// post->first-wire-byte (queue), first->last wire byte (wire), and
// post->completion (total) land in these.
constexpr uint64_t kStageHistBounds[7] = {50, 200, 1000, 5000, 20000, 100000, 1000000};
constexpr int kStageHistBuckets = 8;

// Per-stream byte counters cap (streams beyond this lump into the last slot;
// default nstreams is 2-8, so 32 covers every sane config).
constexpr int kMaxStreamStats = 32;

// Fault-injection action slots for tpunet_faults_injected_total (indices
// match FaultAction in src/fault.h; 0 is unused).
constexpr int kFaultActionSlots = 5;

// Serving-tier queue-depth gauge slots (tpunet_serve_queue_depth{tier=...}):
// router admission queue, prefill backlog, decode slots+pending.
constexpr int kServeTierCount = 3;

// Elastic-churn rewire phases (tpunet_rewire_duration_us{phase=...}):
// detect, quiesce, rendezvous, rewire — the recovery pipeline's stages
// (docs/DESIGN.md "Elastic churn").
constexpr int kRewirePhaseCount = 4;

// Membership-churn event kinds (tpunet_churn_events_total{kind=...}):
// kill, join, shrink, grow, readmit.
constexpr int kChurnKindCount = 5;

// Live weight-swap phases (tpunet_weight_swap_duration_us{phase=...}):
// announce, broadcast, verify, flip — the publication pipeline's stages
// (docs/DESIGN.md "Live weight updates").
constexpr int kSwapPhaseCount = 4;

// Weight-swap event kinds (tpunet_swap_events_total{kind=...}):
// publish, commit, abort, retry, mismatch.
constexpr int kSwapKindCount = 5;

// DCN-bridge callback kinds (tpunet_bridge_{calls,bytes}_total{kind=...}):
// all_reduce, all_reduce_start, all_reduce_finish, all_gather,
// reduce_scatter, all_to_all, broadcast, neighbor_exchange — the collectives
// tpunet/interop.py stages through a host callback (docs/DESIGN.md §3).
constexpr int kBridgeKindCount = 8;

// QoS traffic-class slots (latency, bulk, control — TrafficClass in qos.h;
// kept as a bare count here so telemetry.h need not include qos.h).
constexpr int kQosClassCount = 3;

// Last getsockopt(TCP_INFO) sample for one stream slot. When several comms
// share a stream index the last-sampled socket wins — gauges describe "a
// live connection at this stream position", which is what stream-skew
// triage needs (per-comm split would be unbounded cardinality).
struct StreamTcpSample {
  uint64_t rtt_us = 0;            // tcpi_rtt
  uint64_t srtt_us = 0;           // EWMA over samples (straggler detector input)
  uint64_t retrans_total = 0;     // tcpi_total_retrans of the sampled socket
  uint64_t cwnd = 0;              // tcpi_snd_cwnd (segments)
  uint64_t delivery_rate_bps = 0; // tcpi_delivery_rate * 8 (0 on old kernels)
  uint64_t min_rtt_us = 0;        // tcpi_min_rtt (0 on old kernels) — the
                                  // per-path RTT floor the static
                                  // TPUNET_STRAGGLER_MIN_RTT_US knob
                                  // approximates; observable per stream so
                                  // heterogeneous-path floors stop being a
                                  // one-size env guess
  bool sampled = false;
};

struct StageHist {
  uint64_t buckets[kStageHistBuckets] = {0};
  uint64_t sum_us = 0;
  uint64_t count = 0;
};

struct MetricsSnapshot {
  uint64_t isend_count = 0;
  uint64_t irecv_count = 0;
  uint64_t isend_bytes = 0;
  uint64_t irecv_bytes = 0;
  uint64_t isend_hist[kHistBuckets] = {0};
  uint64_t irecv_hist[kHistBuckets] = {0};
  uint64_t inflight = 0;        // requests posted but not yet test()ed done
  uint64_t failed_requests = 0;
  // Failure-containment counters (docs/DESIGN.md "Failure model"):
  // injected faults by action, data-stream failovers survived, and CRC32C
  // chunk mismatches detected.
  uint64_t faults_injected[kFaultActionSlots] = {0};
  uint64_t stream_failovers = 0;
  uint64_t crc_errors = 0;
  // Bytes moved per data-stream index, all comms aggregated — the observable
  // form of the rotating-cursor fairness property (the reference exposed
  // per-stream effective-time observers instead, nthread:343-348).
  uint64_t stream_tx_bytes[kMaxStreamStats] = {0};
  uint64_t stream_rx_bytes[kMaxStreamStats] = {0};
  // QoS accounting (docs/DESIGN.md "Transport QoS"): bytes per traffic
  // class and direction (the receiver learns the class from the preamble
  // nibble), time chunks waited for wire credit in the DRR scheduler, and
  // grants that jumped an older waiter of another class.
  uint64_t qos_bytes[kQosClassCount][2] = {};  // [class][tx=0, rx=1]
  StageHist qos_wait_us[kQosClassCount];
  uint64_t qos_preempts[kQosClassCount] = {0};
  // Deep-observability additions (docs/DESIGN.md "Observability"):
  StreamTcpSample stream_tcp_tx[kMaxStreamStats];
  StreamTcpSample stream_tcp_rx[kMaxStreamStats];
  // Jain's index over windowed per-stream bytes, per traffic class — the
  // paper's per-stream fairness claim reported WITHIN a class, so bulk's
  // deliberate deprioritization can't read as striping unfairness.
  double fairness_tx[kQosClassCount] = {1.0, 1.0, 1.0};
  double fairness_rx[kQosClassCount] = {1.0, 1.0, 1.0};
  uint64_t straggler_events = 0;
  StageHist req_queue_us;       // post -> first wire byte
  StageHist req_wire_us;        // first -> last wire byte
  StageHist req_total_us;       // post -> completion
  // Lane-striping accounting (docs/DESIGN.md "Lanes & adaptive striping"):
  // the stripe scheduler's current per-lane weight and measured service
  // rate (last writer wins across comms — like the TCP slots, the gauges
  // describe "a live lane at this index"), payload bytes per lane and
  // direction, and weight-vector epochs published (re-stripe events).
  uint64_t lane_weight[kMaxStreamStats] = {0};
  uint64_t lane_rate_bps[kMaxStreamStats] = {0};
  uint64_t lane_bytes[kMaxStreamStats][2] = {};  // [lane][tx=0, rx=1]
  uint64_t restripe_events = 0;
  // Intra-host shared-memory transport (docs/DESIGN.md "Intra-host shared
  // memory"): payload bytes moved through SHM ring segments per direction
  // (deliberately NOT folded into the TCP stream/QoS byte counters, so
  // "the intra-host stage moved zero TCP bytes" is provable straight off
  // the counters) and futex wake syscalls issued by the ring protocol
  // (bytes/wakeup is the ring's syscalls/MiB analogue).
  uint64_t shm_bytes[2] = {0, 0};  // [tx=0, rx=1]
  uint64_t shm_wakeups = 0;
  // Bytes the SHM receive path reduced as they landed (Net::irecv_reduce);
  // a part of reduce_bytes.
  uint64_t shm_reduce_bytes = 0;
  // Serving-tier SLO accounting (docs/DESIGN.md "Serving tier"): per-request
  // time-to-first-token and time-per-output-token histograms fed by the
  // router/decode workers through tpunet_c_serve_observe, plus instantaneous
  // per-tier queue depths (tpunet_c_serve_queue_depth).
  StageHist req_ttft_us;        // request admission -> first token
  StageHist req_tpot_us;        // mean inter-token gap after the first
  uint64_t serve_queue_depth[kServeTierCount] = {0};
  // Elastic-churn accounting (docs/DESIGN.md "Elastic churn"): per-phase
  // rewire duration histograms fed through tpunet_c_rewire_observe by the
  // elastic layer, membership-churn events by kind, and the live world
  // size as this rank last saw it (0 until a churn-aware job reports).
  StageHist rewire_us[kRewirePhaseCount];
  uint64_t churn_events[kChurnKindCount] = {0};
  uint64_t world_size = 0;
  // Live weight-update accounting (docs/DESIGN.md "Live weight updates"):
  // per-phase swap duration histograms fed through tpunet_c_swap_observe
  // by the publication layer, swap events by kind, and the checkpoint
  // version this rank serves (0 until a versioned tier reports).
  StageHist swap_us[kSwapPhaseCount];
  uint64_t swap_events[kSwapKindCount] = {0};
  uint64_t weight_version = 0;
  // DCN-bridge accounting (docs/DESIGN.md §3 "JAX seam"): host callbacks
  // the program's io_callback path ran, and the operand bytes that crossed
  // it, by collective kind. The FFI path never feeds these.
  uint64_t bridge_calls[kBridgeKindCount] = {0};
  uint64_t bridge_bytes[kBridgeKindCount] = {0};
  // The boundary exchange's chunks (tpunet.interop.host_all_reduce): how
  // many crossed, and the most that were between the start of their copy to
  // the host and the return of their device_put at one time.
  uint64_t bridge_chunks[kBridgeKindCount] = {0};
  uint64_t bridge_chunks_in_flight_max[kBridgeKindCount] = {0};
  // Minor page faults of the whole process across boundary exchanges: what
  // the exchange's host blocks cost when their pages are new every step.
  uint64_t bridge_minor_faults[kBridgeKindCount] = {0};
  // Zero-copy data-path counters (docs/DESIGN.md "Data path"): wire syscalls
  // indexed by utils.h IoOp (send, recv, sendmsg, recvmsg) and bytes
  // produced by the reduction kernels. syscalls/MiB is derived from these in
  // benchmarks/engine_p2p.py — the fragmentation signal the 1-core sandbox
  // cannot noise out the way it noises GB/s.
  uint64_t engine_syscalls[4] = {0};
  uint64_t reduce_bytes = 0;
  // Compressed-collectives accounting (docs/DESIGN.md "Compressed
  // collectives"): encoded bytes per codec and direction, plus the f32
  // payload bytes the encoded forms stood in for. The wire-compression
  // ratio (tpunet_codec_wire_ratio) is encoded/payload — the noise-immune
  // proof that bf16 halved (int8: quartered) the ring's DCN bytes.
  uint64_t codec_bytes[2][2] = {{0, 0}, {0, 0}};  // [bf16,int8][tx,rx]
  uint64_t codec_payload_bytes[2] = {0, 0};       // [tx,rx]
  // Schedule-dispatch accounting (docs/DESIGN.md "Schedules & algorithm
  // selection"): sequential collective wire rounds executed by this rank
  // per schedule, and dispatch decisions per (collective, resolved
  // schedule). Slot i maps to CollAlgo i+1 (ring, rhd, tree — kAuto never
  // executes); kind slots are CollKind order (allreduce, broadcast). These
  // counters carry the small-message latency claim: ring AllReduce is
  // 2(W-1) rounds where rhd is 2*log2(W') and tree <= 2*ceil(log2 W).
  // Slots 0-2 map to CollAlgo 1-3 (ring, rhd, tree); slots 3-4 are the
  // hierarchical schedule's two stages (algo="hier.intra"/"hier.inter" —
  // the split is the point: hier's claim is that the inter slot, the DCN
  // wire rounds, shrinks while intra rides shared memory); slots 5-6 are
  // the hierarchical AllToAll's two stages (algo="a2a.intra"/"a2a.inter").
  // Selected slots 0-5 map to CollAlgo 1-6 (ring, rhd, tree, hier,
  // hier_a2a, pairwise); kind slots are CollKind order (allreduce,
  // broadcast, alltoall).
  uint64_t coll_steps[7] = {0, 0, 0, 0, 0, 0, 0};
  uint64_t coll_algo_selected[3][6] = {};
  // AllToAll wire bytes per [stage][dir] (tpunet_a2a_bytes_total: stage 0 =
  // intra regroup, 1 = inter DCN transpose, 2 = flat mesh/relay; dir tx=0,
  // rx=1) — the counter family every hierarchical-AllToAll byte claim is
  // gated on (docs/DESIGN.md "Hierarchical AllToAll").
  uint64_t a2a_bytes[3][2] = {};
  double uptime_s = 0;          // for bytes/s derivation
};

class Telemetry {
 public:
  static Telemetry& Get();

  // Always-on counter hooks (lock-free). Span tracking only when tracing.
  // `owner` disambiguates engine-local request ids across Net instances.
  void OnRequestStart(uint64_t owner, bool is_send, uint64_t comm, uint64_t req,
                      uint64_t nbytes);
  void OnRequestDone(uint64_t owner, uint64_t req, bool failed);
  // Engine hot-path hook: `nbytes` moved on data-stream `stream_idx`
  // (relaxed atomic add; indices >= kMaxStreamStats clamp to the last slot).
  // `cls` is the comm's TrafficClass int (default bulk) — it feeds both the
  // per-class byte counters and the class-split fairness windows.
  void OnStreamBytes(bool is_send, uint64_t stream_idx, uint64_t nbytes,
                     int cls = 1);
  // QoS scheduler hooks (qos.cc): one queue-wait sample per gated chunk,
  // and one preemption event per out-of-arrival-order grant.
  void OnQosQueueWait(int cls, uint64_t wait_us);
  void OnQosPreempt(int cls);
  // Rate-limited TCP_INFO sampler: called from the engines' data paths after
  // chunk IO with the live socket. Costs one clock read + one relaxed atomic
  // compare when the slot's sampling window has not elapsed; otherwise does
  // the getsockopt, updates the slot's gauges, and runs the straggler check.
  void MaybeSampleStream(bool is_send, uint64_t stream_idx, int fd);
  // Straggler-detector verdict for one stream slot (relaxed read of the
  // hysteresis flag the sampler maintains) — the lane adaptation loop's
  // demotion trigger (docs/DESIGN.md "Lanes & adaptive striping").
  bool StreamStraggling(bool is_send, uint64_t stream_idx) const;
  // Lane-striping hooks (lane-mode comms only; docs/DESIGN.md "Lanes &
  // adaptive striping"): current stripe weight / measured service rate per
  // lane (gauges, last writer wins), payload bytes per lane and direction,
  // and one restripe event per weight-vector epoch published.
  void OnLaneWeight(uint64_t lane, uint64_t weight);
  void OnLaneRate(uint64_t lane, uint64_t bps);
  void OnLaneBytes(bool is_send, uint64_t lane, uint64_t nbytes);
  void OnRestripe();
  // Intra-host SHM transport hooks (shm_engine.cc): payload bytes moved
  // through a ring segment, and futex wake syscalls the ring issued.
  void OnShmBytes(bool is_send, uint64_t nbytes);
  void OnShmWakeup();
  // Bytes a reducing receive (Net::irecv_reduce) reduced as they landed.
  void OnShmReduceBytes(uint64_t nbytes);
  // Stage-latency accounting, called by the engines when a successful request
  // is consumed by test()/wait(). Timestamps are MonotonicUs(); completion
  // time is "now". post_us == 0 (no stamp) is ignored.
  void OnRequestStages(uint64_t post_us, uint64_t first_wire_us, uint64_t last_wire_us);
  // Collective phase span (collectives.cc): buffered into the trace file as
  // a Chrome-trace X event tagged {comm_id, coll_seq} — the cross-rank join
  // key. No-op when tracing is off (callers should pre-check
  // tracing_enabled() to skip building the phase string).
  void OnCollPhase(uint64_t comm_id, uint64_t coll_seq, const char* phase,
                   uint64_t start_us, uint64_t dur_us, uint64_t nbytes);
  // Collective part span (coll_comm.h PhaseSpan::Part): one piece of a
  // phase's time on the thread that ran it — "coll.wait_peer",
  // "coll.wait_wire" (`dir` "recv" or "send") or "coll.reduce". Tagged
  // {phase, coll}, never {comm_id, coll_seq}: those mark whole phases for
  // merge_traces() and the ring readers. No-op when tracing is off.
  void OnCollPart(const char* part, uint64_t comm_id, uint64_t coll_seq,
                  const char* phase, const char* dir, uint64_t start_us,
                  uint64_t dur_us);
  // The first-wire stamp of the last request THIS thread consumed (handed
  // over by OnRequestStages; 0 when it had none), cleared by the take. A
  // collective's recv wait splits at it (coll.wait_peer / coll.wait_wire).
  static uint64_t TakeConsumedFirstWireUs();
  // Program span (tpunet.telemetry.span through tpunet_c_trace_span): a
  // host-side span of the Python layer (the DCN bridge's callback, fit()'s
  // loop) buffered into the SAME trace file, stamped by the caller with
  // MonotonicUs()'s clock. Tagged {seq, parent}, never {comm_id, coll_seq}:
  // those stay the collective phases' join key. `parent`/`kind` may be
  // empty, `step` and `chunk` < 0 mean none. Returns false when tracing is
  // off.
  bool OnProgramSpan(const char* name, uint64_t start_us, uint64_t dur_us,
                     uint64_t seq, uint64_t nbytes, const char* parent,
                     const char* kind, int64_t step, int64_t chunk);
  // One host callback of the DCN bridge (tpunet_c_bridge_call): `kind`
  // indexes kBridgeKindCount, `nbytes` is the operand's size.
  void OnBridgeCall(int kind, uint64_t nbytes);
  // One boundary exchange's chunks (tpunet_c_bridge_chunks): `chunks`
  // crossed, at most `in_flight` of them at one time (kept as a maximum).
  void OnBridgeChunks(int kind, uint64_t chunks, uint64_t in_flight);
  // One boundary exchange's minor page faults (tpunet_c_bridge_minor_faults).
  void OnBridgeMinorFaults(int kind, uint64_t faults);
  // Failure-containment hooks (cold paths). `action` indexes FaultAction.
  void OnFaultInjected(int action);
  void OnStreamFailover();
  void OnCrcError();
  // Serving-tier SLO hooks (tpunet_c_serve_*): `kind` 0 = TTFT, 1 = TPOT
  // (both microseconds, observed into the request stage-latency bucket
  // layout); `tier` indexes kServeTierCount (router, prefill, decode).
  void OnServeLatency(int kind, uint64_t us);
  void OnServeQueueDepth(int tier, uint64_t depth);
  // Elastic-churn hooks (tpunet_c_rewire_observe / tpunet_c_churn_event /
  // tpunet_c_world_size): `phase` indexes kRewirePhaseCount, `kind` indexes
  // kChurnKindCount, `world` is the live communicator's world size.
  void OnRewirePhase(int phase, uint64_t us);
  void OnChurnEvent(int kind);
  void OnWorldSize(uint64_t world);
  // Live weight-update hooks (tpunet_c_swap_observe / tpunet_c_swap_event /
  // tpunet_c_weight_version): `phase` indexes kSwapPhaseCount, `kind`
  // indexes kSwapKindCount, `version` is the serving checkpoint version.
  void OnSwapPhase(int phase, uint64_t us);
  void OnSwapEvent(int kind);
  void OnWeightVersion(uint64_t version);
  // Bound port of the on-demand /metrics listener (0 = no listener). With
  // TPUNET_METRICS_PORT=0 the listener binds an EPHEMERAL port and this is
  // the only way to learn it (multi-tier loopback tests scrape both tiers).
  int MetricsPort() const;

  MetricsSnapshot Snapshot() const;
  // Prometheus text exposition of the snapshot (also what the push thread
  // sends and the scrape listener serves). Every family carries adjacent
  // # HELP / # TYPE lines (text-format lint clean).
  std::string PrometheusText() const;
  // Zero every counter/histogram/gauge (trace spans and the in-flight gauge
  // are untouched) so tests and benchmark warmups don't bleed into
  // measurement windows. Also restarts the uptime/fairness windows.
  void Reset();

  bool tracing_enabled() const { return trace_enabled_.load(std::memory_order_relaxed); }
  // Runtime-(re)target tracing at `dir` (empty = flush and disable). Used by
  // tpunet_c_trace_set_dir() / telemetry.profile() so a profile can start
  // after the library loaded without TPUNET_TRACE_DIR.
  bool SetTraceDir(const std::string& dir);
  // Write buffered spans to the trace file; called on buffer pressure, from
  // tpunet_c_trace_flush(), and at process exit (atexit — the singleton is
  // leaked so its destructor never runs). The file is valid JSON after every
  // flush. Returns false when the trace file could not be written (spans are
  // dropped); true on success or when tracing is disabled.
  bool FlushTrace();
  // Stop the push/scrape threads and flush; atexit hook (safe to call
  // repeatedly).
  void ShutdownForExit();

  ~Telemetry();

 private:
  Telemetry();
  // Accept loop of the on-demand /metrics listener; owns (and closes) lfd.
  void ScrapeLoop(int lfd);
  struct Impl;
  std::unique_ptr<Impl> impl_;
  std::atomic<bool> trace_enabled_{false};
};

// Decorator installed by CreateEngine() around the selected engine so both
// engines (and any future one) report identically.
std::unique_ptr<Net> WrapWithTelemetry(std::unique_ptr<Net> inner);

}  // namespace tpunet

#endif  // TPUNET_TELEMETRY_H_
