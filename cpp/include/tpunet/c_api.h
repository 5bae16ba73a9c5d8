/* tpunet stable C ABI.
 *
 * Mirror of the reference's 13 extern "C" functions (reference:
 * src/lib.rs:19-392 bagua_net_c_* and cc/bagua_net.h:37-111), renamed
 * tpunet_c_*, with the reference's quirks fixed:
 *   - no global big-lock serializing every call (reference lib.rs:14-16);
 *   - request ids are freed when test() reports done (reference leaked one
 *     8-byte heap id per request, cc/bagua_net.cc:111-121);
 *   - property strings are owned by the instance and freed with the same
 *     allocator that made them (reference mixed Rust CString with C++
 *     delete, cc/bagua_net.cc:15-21);
 *   - multiple instances allowed (reference: one global singleton);
 *   - tpunet_c_last_error() exposes the failure detail per thread.
 *
 * Error codes (reference doc comments lib.rs:61-63,131-135,290-294):
 *   0 success, -1 null pointer, -2 invalid argument, -3 inner error.
 * Buffer lifetime contract: data passed to isend/irecv must stay alive and
 * unmoved until test() reports the request done (reference lib.rs:251,279).
 */
#ifndef TPUNET_C_API_H_
#define TPUNET_C_API_H_

#include <stdint.h>

#ifdef __cplusplus
extern "C" {
#endif

#define TPUNET_OK 0
#define TPUNET_ERR_NULL -1
#define TPUNET_ERR_INVALID -2
#define TPUNET_ERR_INNER -3
/* Failure-model codes (docs/DESIGN.md "Failure model"): */
/* per-chunk CRC32C mismatch (TPUNET_CRC=1) — the request failed but the
 * comm is still usable (not a disconnect). */
#define TPUNET_ERR_CORRUPT -4
/* progress watchdog (TPUNET_PROGRESS_TIMEOUT_MS): zero bytes moved for a
 * full window — treat the peer as stuck (same recovery as dead). */
#define TPUNET_ERR_TIMEOUT -5
/* peer speaks a different tpunet wire-framing version. */
#define TPUNET_ERR_VERSION -6
/* collective wire-codec mismatch (TPUNET_WIRE_DTYPE / wire_dtype): the
 * ranks of a group disagree on the f32 wire compression codec. Raised at
 * communicator wiring time by the codec handshake on EVERY rank, before any
 * payload could be mis-decoded. */
#define TPUNET_ERR_CODEC -7
/* QoS admission backpressure (TPUNET_QOS_INFLIGHT_BYTES): the send's
 * traffic class already has its in-flight byte budget posted. Nothing was
 * enqueued or charged — retry after in-flight work drains (the serve
 * router replays front-of-queue). docs/DESIGN.md "Transport QoS". */
#define TPUNET_ERR_QOS_ADMISSION -8
/* Elastic rewire failure (docs/DESIGN.md "Elastic churn"): a mid-run
 * membership rewire exceeded TPUNET_REWIRE_TIMEOUT_MS or the churn engine
 * aborted recovery. The old communicator is already finalized; the caller
 * owns the retry-or-die decision — never a hang. */
#define TPUNET_ERR_REWIRE -9
/* Live weight-swap failure (docs/DESIGN.md "Live weight updates"): a
 * version publication aborted — publisher/receiver death mid-broadcast,
 * cross-rank CRC32C digest disagreement (flip refused fleet-wide), or the
 * swap exceeding TPUNET_SWAP_TIMEOUT_MS. The PREVIOUS version keeps
 * serving; the partial staged version was discarded. Retryable. */
#define TPUNET_ERR_WEIGHT_SWAP -10

/* 64-byte opaque rendezvous blob: the serialized listen sockaddr, sized to
 * NCCL's handle budget (reference: cc/nccl_types.h:44). Ship it to the
 * connecting side out-of-band (bootstrap). */
typedef struct tpunet_socket_handle {
  uint8_t data[64];
} tpunet_socket_handle_t;

/* Reference: NCCLNetPropertiesC (lib.rs:41-55). Strings are owned by the
 * instance and live until tpunet_c_destroy. */
typedef struct tpunet_net_properties {
  const char* name;
  const char* pci_path;
  uint64_t guid;
  int32_t ptr_support; /* 1 = host memory */
  int32_t speed_mbps;
  int32_t port;
  int32_t max_comms;
} tpunet_net_properties_t;

/* Engine selected by env TPUNET_IMPLEMENT in {BASIC (default), EPOLL}. */
int32_t tpunet_c_create(uintptr_t* out_instance);
/* As tpunet_c_create, pinning the QoS traffic class every comm this engine
 * CONNECTS will carry — traffic_class in {"latency","bulk","control"};
 * NULL or "" defers to TPUNET_TRAFFIC_CLASS (default bulk). The class
 * nibble rides the connect preamble, so the far side's recv comm adopts it
 * (sender's class wins, like nstreams). Unknown names are
 * TPUNET_ERR_INVALID. docs/DESIGN.md "Transport QoS". */
int32_t tpunet_c_create_ex(const char* traffic_class, uintptr_t* out_instance);
int32_t tpunet_c_destroy(uintptr_t* instance);

int32_t tpunet_c_devices(uintptr_t instance, int32_t* ndev);
int32_t tpunet_c_get_properties(uintptr_t instance, int32_t dev,
                                tpunet_net_properties_t* props);

int32_t tpunet_c_listen(uintptr_t instance, int32_t dev,
                        tpunet_socket_handle_t* handle, uintptr_t* listen_comm);
int32_t tpunet_c_connect(uintptr_t instance, int32_t dev,
                         const tpunet_socket_handle_t* handle, uintptr_t* send_comm);
int32_t tpunet_c_accept(uintptr_t instance, uintptr_t listen_comm,
                        uintptr_t* recv_comm);

int32_t tpunet_c_isend(uintptr_t instance, uintptr_t send_comm, const void* data,
                       uint64_t nbytes, uintptr_t* request);
int32_t tpunet_c_irecv(uintptr_t instance, uintptr_t recv_comm, void* data,
                       uint64_t nbytes, uintptr_t* request);
/* done: 0/1 out-flag; nbytes: actual message size once done (may be smaller
 * than the posted recv buffer). On done the request id is consumed. */
int32_t tpunet_c_test(uintptr_t instance, uintptr_t request, uint8_t* done,
                      uint64_t* nbytes);
/* Blocking companion to test(): parks until the request settles (condvar,
 * no CPU burn) and consumes it. nbytes as in tpunet_c_test. */
int32_t tpunet_c_wait(uintptr_t instance, uintptr_t request, uint64_t* nbytes);

int32_t tpunet_c_close_send(uintptr_t instance, uintptr_t send_comm);
int32_t tpunet_c_close_recv(uintptr_t instance, uintptr_t recv_comm);
int32_t tpunet_c_close_listen(uintptr_t instance, uintptr_t listen_comm);

/* Thread-local message for the last TPUNET_ERR_* returned on this thread. */
const char* tpunet_c_last_error(void);

/* ---- Chaos / integrity tooling ----------------------------------------
 * Deterministic fault injection (src/fault.h): parse `spec` (e.g.
 * "stream=1:after_bytes=1M:action=close") and arm it process-wide for every
 * engine's send/recv hot path. One fault at a time; re-arming replaces and
 * resets the byte counters. NULL or "" clears. Returns TPUNET_ERR_INVALID
 * (with tpunet_c_last_error() naming the bad token) on a malformed spec.
 * TPUNET_FAULT_SPEC arms the same slot at engine creation.
 *
 * The spec may also be a ';'-separated SCRIPT whose churn segments
 * ("churn:at_step=N:rank=K:action=kill|join") arm the process-wide churn
 * script (docs/DESIGN.md "Elastic churn") — deterministic scripted
 * membership churn, polled at step boundaries rather than applied on the
 * IO path. Swap segments ("swap:at_step=N:action=publish|corrupt|die")
 * likewise arm the process-wide weight-swap chaos script (docs/DESIGN.md
 * "Live weight updates"). At most one classic fault segment may ride
 * along. */
int32_t tpunet_c_fault_inject(const char* spec);
int32_t tpunet_c_fault_clear(void);
/* One-shot churn-script poll at a step boundary: fires (and consumes) the
 * first armed event with at_step <= step targeting `rank` (or rank=*) and
 * returns its action — 0 none, 1 kill (the polling rank must die NOW),
 * 2 join (a new rank enters the world; supervisor/joiner-side verdict).
 * Fired latches survive engine rebuilds: the rewires a churn script causes
 * must not re-fire the events the job already recovered from. */
int32_t tpunet_c_churn_poll(uint64_t step, int64_t rank);
/* Armed churn events not yet fired (the churn smoke lane's completeness
 * gate: a finished scripted run must report 0). */
int32_t tpunet_c_churn_pending(void);
/* One-shot swap-script poll at a step boundary (weight hot-swap chaos,
 * "swap:at_step=N:action=publish|corrupt|die" segments of the fault
 * script): fires (and consumes) the first armed event with at_step <= step
 * and returns its action — 0 none, 1 publish (the publisher must start a
 * weight publication NOW), 2 corrupt (the polling receiver must corrupt
 * its received weight bytes before digesting — the flip-refusal drill),
 * 3 die (the polling rank must die NOW, mid-broadcast when timed so).
 * Unlike churn there is no rank clause: each process arms its own script
 * via TPUNET_FAULT_SPEC. Fired latches survive swap retries. */
int32_t tpunet_c_swap_poll(uint64_t step);
/* Armed swap events not yet fired (the swap smoke lane's completeness
 * gate: a finished scripted run must report 0). */
int32_t tpunet_c_swap_pending(void);
/* CRC32C (Castagnoli) of `data`, seeded with `seed` (0 = fresh; chain for
 * discontiguous buffers). Exposed for golden-vector tests and so Python
 * tooling can pre-verify payloads against the wire trailers. */
uint32_t tpunet_c_crc32c(const void* data, uint64_t nbytes, uint32_t seed);
/* Stable host identity (never 0): hash of TPUNET_HOST_ID when set (the
 * fake-host override that splits one box into testable "hosts"), else of
 * the kernel boot id, else of the hostname. Two processes report the same
 * id iff they can share a memory segment — the locality verdict behind the
 * SHM transport handshake (TPUNET_SHM=1) and the hierarchical collective's
 * host grouping. Exposed so Python tests can pin the derivation. */
uint64_t tpunet_c_host_id(void);
/* Elementwise reduction dst[i] = a[i] op b[i] over n elements — the
 * runtime-dispatched (SIMD when the CPU has it, scalar otherwise) kernel the
 * ring collectives run post-wire, exposed so SIMD-vs-scalar equivalence
 * goldens can pin it from Python. dst may alias a (in-place accumulate).
 * dtype: 0=f32 1=f64 2=bf16 3=i32 4=i64 5=u8; op: 0=sum 1=prod 2=min 3=max.
 * Returns TPUNET_ERR_INVALID for an unknown dtype/op or a NULL buffer with
 * n > 0. */
int32_t tpunet_c_reduce(void* dst, const void* a, const void* b, uint64_t n,
                        int32_t dtype, int32_t op);
/* ---- Wire codecs (compressed ring collectives) -------------------------
 * The encode/decode kernels the ring runs at every compressed wire hop
 * (codec: 0=f32 passthrough, 1=bf16 RNE, 2=int8 block-scaled — see
 * docs/DESIGN.md "Compressed collectives"), exposed so Python golden tests
 * can pin the wire format and the documented int8 error bound without a
 * socket in sight. n counts f32 ELEMENTS. */
/* Encoded byte count for n f32 elements (0 for an unknown codec). */
uint64_t tpunet_c_codec_wire_bytes(int32_t codec, uint64_t n);
/* Encode n f32 elements from src into dst (dst_cap must be >= the wire
 * byte count; TPUNET_ERR_INVALID otherwise). */
int32_t tpunet_c_codec_encode(int32_t codec, const void* src, uint64_t n,
                              void* dst, uint64_t dst_cap);
/* Decode a wire buffer of n encoded f32 elements into dst (n floats). */
int32_t tpunet_c_codec_decode(int32_t codec, const void* wire, uint64_t n,
                              void* dst);

/* ---- Lane striping (docs/DESIGN.md "Lanes & adaptive striping") ---------
 * Pure views of the weighted stripe scheduler so Python goldens can pin the
 * chunk->stream layout both sides derive — no sockets involved. */
/* Parse a TPUNET_LANES spec ("addr=10.0.0.1:w=4,addr=10.0.1.1:w=1"; a lane
 * may omit either key) and echo the normalized form, one lane per line:
 * "lane=<i> addr=<a|-> w=<n>". Malformed specs are TPUNET_ERR_INVALID with
 * the offending token in tpunet_c_last_error(). Returns the full text
 * length (the tpunet_c_metrics_text buffer-sizing contract). */
int32_t tpunet_c_lane_parse(const char* spec, char* out, uint64_t cap);
/* The chunk->stream assignment a message of `len` bytes gets under the
 * weighted stripe scheduler: `weights` is a comma-separated per-stream
 * weight list (1..255 each; its length is the stream count), `cursor` the
 * comm's rotation cursor at message start. Writes the comma-separated
 * stream index per chunk (empty for len == 0). Both transport engines
 * derive layouts from exactly this arithmetic — the golden tests pin that
 * sender and receiver agree for every (len, min_chunksize, weights, cursor)
 * without layout metadata on the wire. Equal weights reproduce the uniform
 * cursor%nstreams rotation bit-for-bit. */
int32_t tpunet_c_stripe_map(uint64_t len, uint64_t min_chunksize,
                            const char* weights, uint64_t cursor, char* out,
                            uint64_t cap);

/* ---- Collectives (ring communicator over the transport) ----------------
 * The layer NCCL provided above the reference plugin (SURVEY §2.3); here it
 * is in-repo: bootstrap rendezvous + ring AllReduce/ReduceScatter/AllGather/
 * Broadcast/Barrier + the neighbor-exchange step sequence parallelism needs.
 * dtype: 0=f32 1=f64 2=bf16 3=i32 4=i64 5=u8; op: 0=sum 1=prod 2=min 3=max.
 * A communicator is single-threaded (one collective at a time); all ranks
 * must call the same collectives in the same order. */
int32_t tpunet_comm_create(const char* coordinator, int32_t rank, int32_t world_size,
                           uintptr_t* comm);
/* As tpunet_comm_create, selecting the wire compression codec for f32
 * collectives — wire_dtype in {"f32","bf16","int8"}; NULL or "" defers to
 * TPUNET_WIRE_DTYPE (default f32) — and the collective schedule: algo in
 * {"auto","ring","rhd","tree","hier"}; NULL or "" defers to TPUNET_ALGO
 * (default auto). "hier" is the two-level schedule (intra-host stage +
 * one-rank-per-host DCN stage; needs >= 2 hosts with uniform ranks/host by
 * the handshake's host ids, else it runs the ring).
 * "auto" dispatches per (collective, payload bytes, world) through
 * built-in thresholds or the TPUNET_DISPATCH_TABLE JSON written by
 * `busbw_sweep --emit-dispatch` (docs/DESIGN.md "Schedules & algorithm
 * selection"). Unknown names are TPUNET_ERR_INVALID. Cross-rank
 * disagreements fail wiring on EVERY rank: TPUNET_ERR_CODEC for the codec,
 * TPUNET_ERR_INVALID for the algo/dispatch-table handshake (ranks on
 * different schedules deadlock — this fails them loudly first). */
/* traffic_class in {"latency","bulk","control"} selects the QoS lane every
 * comm the communicator wires will carry; NULL or "" defers to
 * TPUNET_TRAFFIC_CLASS (default bulk). The class byte rides the same
 * bootstrap handshake as the codec/algo: a cross-rank disagreement is
 * TPUNET_ERR_INVALID on EVERY rank. */
int32_t tpunet_comm_create_ex(const char* coordinator, int32_t rank,
                              int32_t world_size, const char* wire_dtype,
                              const char* algo, const char* traffic_class,
                              uintptr_t* comm);
/* Negotiated wire codec of a live communicator: 0=f32, 1=bf16, 2=int8. */
int32_t tpunet_comm_wire_dtype(uintptr_t comm, int32_t* wire_dtype);
/* Process-default communicator for callers that cannot thread a handle —
 * the XLA FFI custom-call collectives look it up at CALL time so elastic
 * recovery can re-point it under already-compiled executables. set(0)
 * clears. get returns 0 when unset. */
int32_t tpunet_comm_set_default(uintptr_t comm);
uintptr_t tpunet_comm_get_default(void);
int32_t tpunet_comm_destroy(uintptr_t* comm);
int32_t tpunet_comm_rank(uintptr_t comm, int32_t* rank, int32_t* world_size);
/* sendbuf may equal recvbuf (in-place). count = elements. */
int32_t tpunet_comm_all_reduce(uintptr_t comm, const void* sendbuf, void* recvbuf,
                               uint64_t count, int32_t dtype, int32_t op);
/* sendbuf: world*recv_count elements; recvbuf: this rank's recv_count. */
int32_t tpunet_comm_reduce_scatter(uintptr_t comm, const void* sendbuf, void* recvbuf,
                                   uint64_t recv_count, int32_t dtype, int32_t op);
/* sendbuf: bytes_per_rank; recvbuf: world*bytes_per_rank rank-ordered. */
int32_t tpunet_comm_all_gather(uintptr_t comm, const void* sendbuf, void* recvbuf,
                               uint64_t bytes_per_rank);
int32_t tpunet_comm_broadcast(uintptr_t comm, void* buf, uint64_t nbytes, int32_t root);
/* sendbuf: world blocks of bytes_per_rank, block j for rank j; recvbuf:
 * world blocks, block j from rank j. sendbuf may equal recvbuf. */
int32_t tpunet_comm_all_to_all(uintptr_t comm, const void* sendbuf, void* recvbuf,
                               uint64_t bytes_per_rank);
/* Typed AllToAll: blocks are count_per_rank ELEMENTS of dtype. f32 blocks
 * honor the communicator's negotiated wire codec — every non-self block is
 * encoded once at the source (int8 scale blocks restart per (src,dst)
 * block) and decoded once at the destination, so results are bit-identical
 * across the pairwise / relay / hierarchical routes and each block's error
 * stays inside the |err| <= amax/254 bound. Non-f32 dtypes (and codec f32)
 * ship uncompressed. docs/DESIGN.md "Hierarchical AllToAll". */
int32_t tpunet_comm_all_to_all_typed(uintptr_t comm, const void* sendbuf,
                                     void* recvbuf, uint64_t count_per_rank,
                                     int32_t dtype);
/* Nonblocking byte-oriented AllToAll: enqueues on the communicator's
 * dedicated mesh worker (pairwise/hier routes) or a ring channel (relay
 * route) and returns a ticket for tpunet_comm_ticket_wait/_test — an async
 * AllToAll overlaps async ring AllReduces on disjoint comms. Same
 * buffer-lifetime and submission-order rules as tpunet_comm_iall_reduce. */
int32_t tpunet_comm_iall_to_all(uintptr_t comm, const void* sendbuf, void* recvbuf,
                                uint64_t bytes_per_rank, uint64_t* ticket);
/* Send to (rank+1)%world while receiving from (rank-1+world)%world. */
int32_t tpunet_comm_neighbor_exchange(uintptr_t comm, const void* sendbuf,
                                      uint64_t send_nbytes, void* recvbuf,
                                      uint64_t recv_nbytes, uint64_t* got);
int32_t tpunet_comm_barrier(uintptr_t comm);
/* Nonblocking AllReduce: enqueues on the comm's worker thread, returns a
 * ticket immediately. Buffers must stay alive until ticket_wait returns.
 * Jobs run in submission order; tickets may be waited in any order; a
 * blocking collective issued while tickets are outstanding fences first. */
int32_t tpunet_comm_iall_reduce(uintptr_t comm, const void* sendbuf, void* recvbuf,
                                uint64_t count, int32_t dtype, int32_t op,
                                uint64_t* ticket);
int32_t tpunet_comm_ticket_wait(uintptr_t comm, uint64_t ticket);
int32_t tpunet_comm_ticket_test(uintptr_t comm, uint64_t ticket, uint8_t* done);

/* ---- Telemetry ---------------------------------------------------------
 * Metrics counters are process-global and always on; spans/push/scrape are
 * gated by env (TPUNET_TRACE_DIR / TPUNET_METRICS_ADDR /
 * TPUNET_METRICS_PORT, rank 0-7 — the reference's gating, nthread:108-130).
 * Deep observability (docs/DESIGN.md "Observability"): per-stream
 * TCP_INFO gauges + Jain fairness + straggler events
 * (TPUNET_TCPINFO_INTERVAL_MS, TPUNET_STRAGGLER_FACTOR), request
 * stage-latency histograms (tpunet_req_{queue,wire,total}_us), and
 * collective phase spans tagged (comm_id, coll_seq, phase). */
/* Write the Prometheus text exposition into buf (NUL-terminated, truncated
 * to cap). Returns the full length (excluding NUL), or a TPUNET_ERR_*. */
int32_t tpunet_c_metrics_text(char* buf, uint64_t cap);
/* Zero every metric counter/histogram/gauge (trace spans and the in-flight
 * gauge are untouched) so tests and benchmark warmups don't bleed counters
 * into measurement windows. */
int32_t tpunet_c_metrics_reset(void);
/* Flush buffered trace spans to the trace file (no-op when disabled). The
 * file is valid Chrome-trace JSON after every flush. */
int32_t tpunet_c_trace_flush(void);
/* Runtime-(re)target tracing at `dir` (tpunet.telemetry.profile()): starts
 * tracing even when TPUNET_TRACE_DIR was unset at load. NULL or "" flushes
 * and disables. */
int32_t tpunet_c_trace_set_dir(const char* dir);
/* Record one PROGRAM span (tpunet.telemetry.span): a host-side span of the
 * Python layer — the DCN bridge's callback, fit()'s loop — into the native
 * tracer's buffer, so it lands in the same tpunet-trace-rank<R>.json as the
 * request and collective phase spans. `start_us`/`dur_us` are on
 * CLOCK_MONOTONIC in microseconds (Python: time.monotonic_ns() // 1000).
 * Args written: seq (the root span's per-process counter, shared by its
 * children), nbytes, and — when given — parent (the enclosing span's name),
 * kind, step, chunk (step, chunk < 0 = none; chunk numbers the pieces of a
 * boundary exchange); never comm_id/coll_seq, the collective phases' join
 * key. `name`, `parent`, `kind`: [A-Za-z0-9_.:-]{1,64} (parent
 * and kind may be NULL or ""). Returns 1 when recorded, 0 when tracing is
 * off (the caller stops calling), or TPUNET_ERR_INVALID. */
int32_t tpunet_c_trace_span(const char* name, uint64_t start_us, uint64_t dur_us,
                            uint64_t seq, uint64_t nbytes, const char* parent,
                            const char* kind, int64_t step, int64_t chunk);
/* Count one host callback of the DCN bridge (tpunet/interop.py's
 * io_callback path) and its operand bytes into
 * tpunet_bridge_{calls,bytes}_total{kind=...}: 0 = all_reduce,
 * 1 = all_reduce_start, 2 = all_reduce_finish, 3 = all_gather,
 * 4 = reduce_scatter, 5 = all_to_all, 6 = broadcast,
 * 7 = neighbor_exchange. */
int32_t tpunet_c_bridge_call(int32_t kind, uint64_t nbytes);
/* Count the chunks of one boundary exchange (tpunet/interop.py's
 * host_all_reduce) into tpunet_bridge_chunks_total{kind=...} and keep the
 * most that were in flight at one time (copy to the host started,
 * device_put not yet returned) in tpunet_bridge_chunks_in_flight_max{kind=...}.
 * `kind` as for tpunet_c_bridge_call. */
int32_t tpunet_c_bridge_chunks(int32_t kind, uint64_t chunks, uint64_t in_flight);
/* Count the minor page faults the process took across one boundary exchange
 * (getrusage(RUSAGE_SELF).ru_minflt, every thread's, read by the caller at
 * the two ends of host_all_reduce's dcn.bridge span) into
 * tpunet_bridge_minor_faults_total{kind=...}. `kind` as for
 * tpunet_c_bridge_call. */
int32_t tpunet_c_bridge_minor_faults(int32_t kind, uint64_t faults);
/* Bound port of the on-demand /metrics listener, or 0 when no listener is
 * up. TPUNET_METRICS_PORT unset/empty = no listener; an explicit 0 binds an
 * EPHEMERAL port (multi-tier loopback: several processes on one box each
 * get their own listener) whose number only this call can report. */
int32_t tpunet_c_metrics_port(void);
/* Serving-tier SLO observation (docs/DESIGN.md "Serving tier"): record one
 * latency sample into the TTFT (kind 0, tpunet_req_ttft_us) or TPOT
 * (kind 1, tpunet_req_tpot_us) histogram. `us` is microseconds. */
int32_t tpunet_c_serve_observe(int32_t kind, uint64_t us);
/* Set the instantaneous queue-depth gauge of a serving tier
 * (tpunet_serve_queue_depth{tier=...}): 0 = router, 1 = prefill,
 * 2 = decode. */
int32_t tpunet_c_serve_queue_depth(int32_t tier, uint64_t depth);
/* ---- Elastic churn observability (docs/DESIGN.md "Elastic churn") -------
 * Record one rewire-phase duration sample into
 * tpunet_rewire_duration_us{phase=...}: 0 = detect (last good collective ->
 * failure classified / join agreed), 1 = quiesce (old comm finalized),
 * 2 = rendezvous (membership sealed + generation published), 3 = rewire
 * (new communicator wired at the new shape). `us` is microseconds. */
int32_t tpunet_c_rewire_observe(int32_t phase, uint64_t us);
/* Count one membership-churn event into tpunet_churn_events_total{kind=...}:
 * 0 = kill (scripted death fired), 1 = join (join request honored),
 * 2 = shrink (world rebuilt smaller), 3 = grow (world rebuilt larger),
 * 4 = readmit (a recovered decode rank re-entered the serving pool). */
int32_t tpunet_c_churn_event(int32_t kind);
/* Set the tpunet_world_size gauge — the live communicator's world as seen
 * by this rank (the churn suite's "world came back" gate). */
int32_t tpunet_c_world_size(uint64_t world);
/* ---- Live weight updates (docs/DESIGN.md "Live weight updates") ---------
 * Record one weight-swap phase duration sample into
 * tpunet_weight_swap_duration_us{phase=...}: 0 = announce (SWAP_BEGIN
 * frames out / receiver armed), 1 = broadcast (chunked bf16 tree broadcast
 * on the bulk class), 2 = verify (cross-rank CRC32C digest agreement),
 * 3 = flip (new BatchServer built, version live). `us` is microseconds. */
int32_t tpunet_c_swap_observe(int32_t phase, uint64_t us);
/* Count one weight-swap event into tpunet_swap_events_total{kind=...}:
 * 0 = publish (a publication attempt started), 1 = commit (every rank
 * agreed and flipped), 2 = abort (staged version discarded — death or
 * timeout), 3 = retry (a failed publication re-attempted), 4 = mismatch
 * (CRC digest disagreement refused the flip fleet-wide). */
int32_t tpunet_c_swap_event(int32_t kind);
/* Set the tpunet_weight_version gauge — the checkpoint version this rank
 * is serving (the swap smoke lane's "v2 reached every rank" gate). */
int32_t tpunet_c_weight_version(uint64_t version);
/* ---- Flight recorder (docs/DESIGN.md §6c) -------------------------------
 * Dump the per-rank flight-recorder ring to
 * <dir>/tpunet-flightrec-rank<R>.json (dir NULL/"" = TPUNET_TRACE_DIR when
 * set at init, else "."). `reason` (NULL = "api") lands in the dump header.
 * Writes the dump path into out_path (NUL-terminated, truncated to cap) and
 * returns its full length — the tpunet_c_metrics_text buffer-sizing
 * contract. TPUNET_ERR_INVALID when the recorder is disabled
 * (TPUNET_FLIGHTREC_EVENTS=0) or the target is unwritable. */
int32_t tpunet_c_flightrec_dump(const char* dir, const char* reason,
                                char* out_path, uint64_t cap);
/* Recorder occupancy: events ever recorded (the ring cursor — monotonic,
 * NOT clamped to capacity) and ring capacity in slots. Both 0 when the
 * recorder is disabled. Either pointer may be NULL. */
int32_t tpunet_c_flightrec_stats(uint64_t* recorded, uint64_t* capacity);

/* ---- Transport QoS introspection (docs/DESIGN.md "Transport QoS") -------
 * Text echo of the process QoS scheduler's parsed config (weights, budgets,
 * wire window) and live state (admitted/in-flight bytes, queue depths) into
 * buf (NUL-terminated, truncated to cap). Returns the full length
 * (excluding NUL) — the buffer-sizing contract of tpunet_c_metrics_text.
 * Lets Python pin that TPUNET_QOS_WEIGHTS / TPUNET_QOS_INFLIGHT_BYTES
 * parsed to what the operator meant. */
int32_t tpunet_c_qos_state(char* buf, uint64_t cap);
/* Deficit-round-robin arithmetic golden: simulate the wire-credit grant
 * order for `chunks` ("class:bytes,class:bytes,...", queued in order) under
 * `weights` (TPUNET_QOS_WEIGHTS grammar) and `window` ("wire=<bytes>");
 * completions retire in grant order. Writes the comma-separated class grant
 * sequence into out (same sizing contract). Pure arithmetic — no sockets,
 * no clocks — so tests can pin strict control priority and the weighted
 * latency/bulk interleave exactly. Malformed specs are TPUNET_ERR_INVALID
 * with the offending token in tpunet_c_last_error(). */
int32_t tpunet_c_qos_drr_golden(const char* weights, const char* window,
                                const char* chunks, char* out, uint64_t cap);

#ifdef __cplusplus
}
#endif

#endif /* TPUNET_C_API_H_ */
