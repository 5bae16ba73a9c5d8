// tpunet telemetry implementation. See include/tpunet/telemetry.h.
#include "tpunet/telemetry.h"

#include <errno.h>
#include <netdb.h>
#include <netinet/in.h>
#include <netinet/tcp.h>
#include <poll.h>
#include <stddef.h>
#include <string.h>
#include <sys/socket.h>
#include <sys/syscall.h>
#include <unistd.h>

#include <algorithm>
#include <atomic>
#include <chrono>
#include <cstdio>
#include <mutex>
#include <thread>
#include <unordered_map>
#include <vector>

#include "dispatch.h"
#include "flightrec.h"
#include "tpunet/mutex.h"
#include "tpunet/utils.h"

namespace tpunet {
namespace {

uint64_t NowUs() { return MonotonicUs(); }

int HistBucket(uint64_t nbytes) {
  for (int i = 0; i < kHistBuckets - 1; ++i) {
    if (nbytes <= kHistBounds[i]) return i;
  }
  return kHistBuckets - 1;
}

int StageBucket(uint64_t us) {
  for (int i = 0; i < kStageHistBuckets - 1; ++i) {
    if (us <= kStageHistBounds[i]) return i;
  }
  return kStageHistBuckets - 1;
}

int64_t RankFromEnv() {
  return static_cast<int64_t>(GetEnvU64("TPUNET_RANK", GetEnvU64("RANK", 0)));
}

// Reference gating: telemetry only for ranks 0-7 with the address var set
// (nthread:108-130).
bool RankGate() {
  int64_t r = RankFromEnv();
  return r >= 0 && r <= 7;
}

std::string Base64(const std::string& in) {
  static const char* tbl = "ABCDEFGHIJKLMNOPQRSTUVWXYZabcdefghijklmnopqrstuvwxyz0123456789+/";
  std::string out;
  size_t i = 0;
  while (i + 2 < in.size()) {
    uint32_t v = (uint8_t(in[i]) << 16) | (uint8_t(in[i + 1]) << 8) | uint8_t(in[i + 2]);
    out += tbl[(v >> 18) & 63];
    out += tbl[(v >> 12) & 63];
    out += tbl[(v >> 6) & 63];
    out += tbl[v & 63];
    i += 3;
  }
  if (i + 1 == in.size()) {
    uint32_t v = uint8_t(in[i]) << 16;
    out += tbl[(v >> 18) & 63];
    out += tbl[(v >> 12) & 63];
    out += "==";
  } else if (i + 2 == in.size()) {
    uint32_t v = (uint8_t(in[i]) << 16) | (uint8_t(in[i + 1]) << 8);
    out += tbl[(v >> 18) & 63];
    out += tbl[(v >> 12) & 63];
    out += tbl[(v >> 6) & 63];
    out += "=";
  }
  return out;
}

// Linux UAPI struct tcp_info layout through tcpi_delivery_rate (the glibc
// copy in <netinet/tcp.h> predates the delivery-rate fields on many
// distros). getsockopt fills min(optlen, kernel size) and reports the filled
// length, so reads past what the running kernel provides are guarded by the
// returned length.
struct TcpInfoCompat {
  uint8_t state, ca_state, retransmits, probes, backoff, options, wscale, flags;
  uint32_t rto, ato, snd_mss, rcv_mss;
  uint32_t unacked, sacked, lost, retrans, fackets;
  uint32_t last_data_sent, last_ack_sent, last_data_recv, last_ack_recv;
  uint32_t pmtu, rcv_ssthresh, rtt, rttvar, snd_ssthresh, snd_cwnd, advmss, reordering;
  uint32_t rcv_rtt, rcv_space;
  uint32_t total_retrans;
  uint64_t pacing_rate, max_pacing_rate, bytes_acked, bytes_received;
  uint32_t segs_out, segs_in;
  uint32_t notsent_bytes, min_rtt, data_segs_in, data_segs_out;
  uint64_t delivery_rate;  // bytes/sec
};

struct Span {
  enum class Kind : uint8_t { kReq, kColl, kInstant, kProg, kPart };
  Kind kind = Kind::kReq;
  bool is_send = false;
  uint64_t comm = 0;    // kReq: comm id | kColl/kPart: comm_id | kInstant: stream idx | kProg: thread id
  uint64_t req = 0;     // kReq: request id | kColl/kPart: coll_seq | kInstant: srtt | kProg: seq
  uint64_t nbytes = 0;  // kReq/kColl/kProg: bytes | kInstant: median srtt
  uint64_t start_us = 0;
  uint64_t dur_us = 0;
  std::string name;     // kColl: phase | kInstant: event name | kProg/kPart: span name
  std::string extra;    // kProg/kPart: further args, ready-made JSON members
};

// Request ids are engine-local (each instance counts from 1), so open spans
// are keyed by (owner instance tag, request id).
using SpanKey = std::pair<uint64_t, uint64_t>;
struct SpanKeyHash {
  size_t operator()(const SpanKey& k) const {
    return std::hash<uint64_t>()(k.first * 0x9e3779b97f4a7c15ull ^ k.second);
  }
};

// Per-stream-slot TCP introspection state: the rate limiter plus the last
// sample's gauges, all relaxed atomics (last writer wins is fine for gauges).
struct StreamTcpState {
  std::atomic<uint64_t> next_sample_us{0};
  std::atomic<uint64_t> rtt_us{0};
  std::atomic<uint64_t> srtt_us{0};
  std::atomic<uint64_t> retrans_total{0};
  std::atomic<uint64_t> cwnd{0};
  std::atomic<uint64_t> delivery_rate_bps{0};
  std::atomic<uint64_t> min_rtt_us{0};  // tcpi_min_rtt (per-path RTT floor)
  std::atomic<uint8_t> sampled{0};
  std::atomic<uint8_t> straggling{0};  // hysteresis: count rising edges only
};

struct StageHistAtomic {
  std::atomic<uint64_t> buckets[kStageHistBuckets] = {};
  std::atomic<uint64_t> sum_us{0};
  std::atomic<uint64_t> count{0};

  void Observe(uint64_t us) {
    buckets[StageBucket(us)].fetch_add(1, std::memory_order_relaxed);
    sum_us.fetch_add(us, std::memory_order_relaxed);
    count.fetch_add(1, std::memory_order_relaxed);
  }
  void SnapshotInto(StageHist* out) const {
    for (int i = 0; i < kStageHistBuckets; ++i) {
      out->buckets[i] = buckets[i].load(std::memory_order_relaxed);
    }
    out->sum_us = sum_us.load(std::memory_order_relaxed);
    out->count = count.load(std::memory_order_relaxed);
  }
  void Reset() {
    for (auto& b : buckets) b.store(0, std::memory_order_relaxed);
    sum_us.store(0, std::memory_order_relaxed);
    count.store(0, std::memory_order_relaxed);
  }
};

double BitsToDouble(uint64_t bits) {
  double d;
  memcpy(&d, &bits, sizeof(d));
  return d;
}
uint64_t DoubleToBits(double d) {
  uint64_t bits;
  memcpy(&bits, &d, sizeof(bits));
  return bits;
}

// Jain's fairness index (sum x)^2 / (n * sum x^2) over the nonzero entries;
// 1.0 when nothing moved (vacuously fair).
double JainIndex(const uint64_t* deltas, int n) {
  double sum = 0, sumsq = 0;
  int active = 0;
  for (int i = 0; i < n; ++i) {
    if (deltas[i] == 0) continue;
    double x = static_cast<double>(deltas[i]);
    sum += x;
    sumsq += x * x;
    ++active;
  }
  if (active == 0 || sumsq == 0) return 1.0;
  return (sum * sum) / (active * sumsq);
}

}  // namespace

struct Telemetry::Impl {
  // Counters: always on, lock-free.
  std::atomic<uint64_t> isend_count{0}, irecv_count{0};
  std::atomic<uint64_t> isend_bytes{0}, irecv_bytes{0};
  std::atomic<uint64_t> isend_hist[kHistBuckets] = {};
  std::atomic<uint64_t> irecv_hist[kHistBuckets] = {};
  std::atomic<uint64_t> inflight{0};
  std::atomic<uint64_t> failed{0};
  // Per-(class, stream) byte cells: tpunet_stream_{tx,rx}_bytes sums the
  // class axis, tpunet_qos_bytes_total sums the stream axis, and the
  // class-split Jain windows read the cells directly — one write site
  // feeds all three views.
  std::atomic<uint64_t> stream_tx[kQosClassCount][kMaxStreamStats] = {};
  std::atomic<uint64_t> stream_rx[kQosClassCount][kMaxStreamStats] = {};
  // QoS scheduler accounting: per-class wire-credit queue-wait histograms
  // and the out-of-arrival-order grant (preemption) counters.
  StageHistAtomic qos_wait[kQosClassCount];
  std::atomic<uint64_t> qos_preempts[kQosClassCount] = {};
  std::atomic<uint64_t> faults_injected[kFaultActionSlots] = {};
  std::atomic<uint64_t> stream_failovers{0};
  std::atomic<uint64_t> crc_errors{0};
  std::atomic<uint64_t> start_us{NowUs()};
  int64_t rank = RankFromEnv();

  // Stage-latency histograms (always on; fed by the engines at request
  // consumption).
  StageHistAtomic req_queue, req_wire, req_total;

  // Serving-tier SLO accounting: TTFT/TPOT histograms fed through
  // tpunet_c_serve_observe by the router/decode workers, plus per-tier
  // queue-depth gauges (last writer wins — instantaneous depths).
  StageHistAtomic req_ttft, req_tpot;
  std::atomic<uint64_t> serve_depth[kServeTierCount] = {};

  // Elastic-churn accounting: per-phase rewire duration histograms, churn
  // events by kind, and the last-reported live world size (gauge).
  StageHistAtomic rewire_phase[kRewirePhaseCount];
  std::atomic<uint64_t> churn_events[kChurnKindCount] = {};
  std::atomic<uint64_t> world_size{0};

  // Live weight-update accounting: per-phase swap duration histograms,
  // swap events by kind, and the serving checkpoint version (gauge).
  StageHistAtomic swap_phase[kSwapPhaseCount];
  std::atomic<uint64_t> swap_events[kSwapKindCount] = {};
  std::atomic<uint64_t> weight_version{0};

  // DCN-bridge accounting: host callbacks of the io_callback path and the
  // operand bytes they staged, by collective kind.
  std::atomic<uint64_t> bridge_calls[kBridgeKindCount] = {};
  std::atomic<uint64_t> bridge_bytes[kBridgeKindCount] = {};
  std::atomic<uint64_t> bridge_chunks[kBridgeKindCount] = {};
  std::atomic<uint64_t> bridge_chunks_in_flight_max[kBridgeKindCount] = {};
  std::atomic<uint64_t> bridge_minor_faults[kBridgeKindCount] = {};

  // TCP introspection (always on unless TPUNET_TCPINFO_INTERVAL_MS=0).
  uint64_t tcp_interval_us =
      GetEnvU64("TPUNET_TCPINFO_INTERVAL_MS", 100) * 1000;
  uint64_t straggler_factor = GetEnvU64("TPUNET_STRAGGLER_FACTOR", 3);
  // RTT floor below which nothing counts as a straggler — loopback and
  // intra-rack RTTs jitter by whole multiples without meaning anything.
  uint64_t straggler_min_rtt_us = GetEnvU64("TPUNET_STRAGGLER_MIN_RTT_US", 1000);
  StreamTcpState tcp_tx[kMaxStreamStats];
  StreamTcpState tcp_rx[kMaxStreamStats];
  std::atomic<uint64_t> straggler_events{0};

  // Lane-striping state (docs/DESIGN.md "Lanes & adaptive striping"): the
  // stripe scheduler's current per-lane weight / measured service rate
  // (last writer wins across comms), per-lane payload bytes, and published
  // weight-vector epochs. lane_weight 0 = "no lane-mode comm ever reported
  // this slot" (lane weights themselves have floor 1), which is the emit
  // gate for the gauge families.
  std::atomic<uint64_t> lane_weight[kMaxStreamStats] = {};
  std::atomic<uint64_t> lane_rate_bps[kMaxStreamStats] = {};
  std::atomic<uint64_t> lane_bytes[kMaxStreamStats][2] = {};
  std::atomic<uint64_t> restripe_events{0};

  // Intra-host SHM transport: ring payload bytes per direction + futex
  // wake syscalls (shm_engine.cc; docs/DESIGN.md "Intra-host shared
  // memory").
  std::atomic<uint64_t> shm_bytes[2] = {};
  std::atomic<uint64_t> shm_wakeups{0};
  std::atomic<uint64_t> shm_reduce_bytes{0};

  // Fairness window (win_mu): Jain's index over per-stream byte deltas
  // between rolls. Rolled lazily from Snapshot() at most once per
  // TPUNET_FAIRNESS_WINDOW_MS; the first roll covers everything since
  // start/Reset (deterministic for tests). win_mu is a leaf lock.
  Mutex win_mu;
  bool win_init GUARDED_BY(win_mu) = false;
  uint64_t win_last_us GUARDED_BY(win_mu) = 0;
  uint64_t fairness_window_us = GetEnvU64("TPUNET_FAIRNESS_WINDOW_MS", 1000) * 1000;
  uint64_t win_tx[kQosClassCount][kMaxStreamStats] GUARDED_BY(win_mu) = {};
  uint64_t win_rx[kQosClassCount][kMaxStreamStats] GUARDED_BY(win_mu) = {};
  std::atomic<uint64_t> fair_tx_bits[kQosClassCount] = {
      DoubleToBits(1.0), DoubleToBits(1.0), DoubleToBits(1.0)};
  std::atomic<uint64_t> fair_rx_bits[kQosClassCount] = {
      DoubleToBits(1.0), DoubleToBits(1.0), DoubleToBits(1.0)};

  // Span tracking (tracing only). span_mu also serializes trace-file writes
  // (FlushTrace) and the trace target swap (SetTraceDir); leaf lock.
  Mutex span_mu;
  std::unordered_map<SpanKey, Span, SpanKeyHash> open_spans GUARDED_BY(span_mu);
  std::vector<Span> done_spans GUARDED_BY(span_mu);
  std::string trace_path GUARDED_BY(span_mu);
  bool trace_header_written GUARDED_BY(span_mu) = false;

  // Threads do not survive fork(): a mismatch in the child means the pusher
  // pthread never existed here and push_mu/span_mu may have been captured
  // mid-lock at fork — skip the whole shutdown handshake there.
  const uint64_t created_fork_gen = ForkGeneration();

  // Push thread.
  std::thread pusher;
  Mutex push_mu;  // leaf: guards only the stop flag
  CondVar push_cv;
  bool stopping GUARDED_BY(push_mu) = false;

  // Counter-timeseries sampler (TPUNET_TS_INTERVAL_MS > 0): appends one full
  // metric snapshot as a JSONL line per interval to
  // tpunet-ts-rank<R>.jsonl — the measurement history benchmarks/sentry.py
  // and offline regression triage replay. Shares push_mu/push_cv/stopping
  // with the pusher for shutdown.
  std::thread ts_sampler;

  // On-demand /metrics scrape listener (TPUNET_METRICS_PORT). The socket is
  // bound SYNCHRONOUSLY in the constructor so the chosen port (ephemeral
  // when the var is set to 0) is readable the moment the singleton exists.
  std::thread scraper;
  std::atomic<bool> scrape_stop{false};
  std::atomic<int> scrape_bound_port{0};
};

Telemetry& Telemetry::Get() {
  static Telemetry* t = new Telemetry();  // leaked on purpose: engines may
  return *t;                              // report during static teardown
}

namespace {
// The leaked singleton's destructor never runs, so final trace flush and
// pusher/scraper shutdown are driven by atexit instead (registered once,
// when any telemetry sink is enabled).
void TelemetryAtExit() { Telemetry::Get().ShutdownForExit(); }
std::once_flag g_atexit_once;
void RegisterAtExit() {
  std::call_once(g_atexit_once, [] { std::atexit(TelemetryAtExit); });
}
}  // namespace

Telemetry::Telemetry() : impl_(new Impl()) {
  std::string trace_dir = GetEnv("TPUNET_TRACE_DIR", GetEnv("BAGUA_NET_JAEGER_ADDRESS", ""));
  if (!trace_dir.empty() && RankGate()) {
    // The BAGUA_NET_JAEGER_ADDRESS fallback accepts the reference's env name
    // but writes local Chrome-trace JSON — there is no Jaeger agent here.
    impl_->trace_path =
        trace_dir + "/tpunet-trace-rank" + std::to_string(impl_->rank) + ".json";
    trace_enabled_.store(true, std::memory_order_relaxed);
    RegisterAtExit();
  }

  std::string addr = GetEnv("TPUNET_METRICS_ADDR", GetEnv("TPUNET_PROMETHEUS_ADDRESS",
                            GetEnv("BAGUA_NET_PROMETHEUS_ADDRESS", "")));
  if (!addr.empty() && RankGate()) {
    RegisterAtExit();
    uint64_t interval_ms = GetEnvU64("TPUNET_METRICS_INTERVAL_MS", 1000);
    if (interval_ms == 0) interval_ms = 1000;
    impl_->pusher = std::thread([this, addr, interval_ms] {
      UserPassAddr upa;
      if (!ParseUserPassAndAddr(addr, &upa)) return;
      auto colon = upa.addr.rfind(':');
      if (colon == std::string::npos) return;
      std::string host = upa.addr.substr(0, colon);
      std::string port = upa.addr.substr(colon + 1);
      std::string auth =
          upa.user.empty() ? "" : "Authorization: Basic " + Base64(upa.user + ":" + upa.pass) + "\r\n";
      std::string path = "/metrics/job/tpunet/rank/" + std::to_string(impl_->rank);
      while (true) {
        {
          // A spurious wakeup inside the interval just pushes one period
          // early — harmless, so no deadline re-arm loop here.
          MutexLock lk(impl_->push_mu);
          if (!impl_->stopping) {
            impl_->push_cv.WaitFor(impl_->push_mu, static_cast<int>(interval_ms));
          }
          if (impl_->stopping) return;
        }
        std::string body = PrometheusText();
        std::string req = "PUT " + path + " HTTP/1.1\r\nHost: " + host +
                          "\r\nContent-Type: text/plain\r\n" + auth +
                          "Content-Length: " + std::to_string(body.size()) +
                          "\r\nConnection: close\r\n\r\n" + body;
        struct addrinfo hints = {};
        hints.ai_socktype = SOCK_STREAM;
        struct addrinfo* res = nullptr;
        if (getaddrinfo(host.c_str(), port.c_str(), &hints, &res) != 0 || !res) continue;
        int fd = ::socket(res->ai_family, SOCK_STREAM, 0);
        if (fd >= 0) {
          if (::connect(fd, res->ai_addr, res->ai_addrlen) == 0) {
            (void)!::send(fd, req.data(), req.size(), MSG_NOSIGNAL);
            char drain[256];
            (void)!::recv(fd, drain, sizeof(drain), MSG_DONTWAIT);
          }
          ::close(fd);
        }
        freeaddrinfo(res);
      }
    });
  }

  // On-demand Prometheus scrape endpoint: GET http://host:PORT/metrics.
  // Each rank needs its own port; the pusher and the listener are
  // independent — either or both may be on. An UNSET (or empty/garbage)
  // var means no listener; an explicit TPUNET_METRICS_PORT=0 binds an
  // EPHEMERAL port — the disaggregated-serving loopback case, where
  // several tiers on one box each need their own listener without port
  // bookkeeping — readable afterwards via tpunet_c_metrics_port(). The
  // bind happens HERE (synchronously) so the chosen port exists the
  // moment the singleton does.
  std::string scrape_env = GetEnv("TPUNET_METRICS_PORT", "");
  char* scrape_end = nullptr;
  uint64_t scrape_port =
      scrape_env.empty() ? 0 : strtoull(scrape_env.c_str(), &scrape_end, 10);
  bool scrape_numeric = !scrape_env.empty() && scrape_end != nullptr &&
                        *scrape_end == '\0';
  if (scrape_numeric && scrape_port < 65536 && RankGate()) {
    int lfd = ::socket(AF_INET, SOCK_STREAM | SOCK_CLOEXEC, 0);
    if (lfd >= 0) {
      int one = 1;
      ::setsockopt(lfd, SOL_SOCKET, SO_REUSEADDR, &one, sizeof(one));
      sockaddr_in sa = {};
      sa.sin_family = AF_INET;
      sa.sin_port = htons(static_cast<uint16_t>(scrape_port));
      sa.sin_addr.s_addr = htonl(INADDR_ANY);
      sockaddr_in got = {};
      socklen_t got_len = sizeof(got);
      if (::bind(lfd, reinterpret_cast<sockaddr*>(&sa), sizeof(sa)) != 0 ||
          ::listen(lfd, 16) != 0 ||
          ::getsockname(lfd, reinterpret_cast<sockaddr*>(&got), &got_len) != 0) {
        fprintf(stderr, "[tpunet] /metrics listener: cannot bind port %llu: %s\n",
                (unsigned long long)scrape_port, strerror(errno));
        ::close(lfd);
        lfd = -1;
      }
      if (lfd >= 0) {
        impl_->scrape_bound_port.store(ntohs(got.sin_port),
                                       std::memory_order_release);
        RegisterAtExit();
        impl_->scraper = std::thread([this, lfd] { ScrapeLoop(lfd); });
      }
    }
  }

  // Counter-timeseries sampler (docs/DESIGN.md §6c): every
  // TPUNET_TS_INTERVAL_MS, append the full Prometheus exposition as one
  // JSONL line ({"t_us":...,"exposition":"..."}) so perf claims have a
  // HISTORY, not just a final scrape. Off by default (0). One final sample
  // is taken at shutdown so runs shorter than one interval still record.
  uint64_t ts_interval_ms = GetEnvU64("TPUNET_TS_INTERVAL_MS", 0);
  if (ts_interval_ms > 0 && RankGate()) {
    RegisterAtExit();
    std::string ts_dir = GetEnv("TPUNET_TRACE_DIR", ".");
    if (ts_dir.empty()) ts_dir = ".";
    std::string ts_path =
        ts_dir + "/tpunet-ts-rank" + std::to_string(impl_->rank) + ".jsonl";
    impl_->ts_sampler = std::thread([this, ts_path, ts_interval_ms] {
      FILE* f = fopen(ts_path.c_str(), "a");
      if (!f) return;
      auto sample = [&] {
        std::string expo = PrometheusText();
        std::string esc;
        esc.reserve(expo.size() + expo.size() / 8);
        for (char ch : expo) {
          if (ch == '"' || ch == '\\') {
            esc += '\\';
            esc += ch;
          } else if (ch == '\n') {
            esc += "\\n";
          } else {
            esc += ch;
          }
        }
        fprintf(f, "{\"t_us\":%llu,\"exposition\":\"%s\"}\n",
                (unsigned long long)NowUs(), esc.c_str());
        fflush(f);
      };
      while (true) {
        {
          MutexLock lk(impl_->push_mu);
          if (!impl_->stopping) {
            impl_->push_cv.WaitFor(impl_->push_mu,
                                   static_cast<int>(ts_interval_ms));
          }
          if (impl_->stopping) break;
        }
        sample();
      }
      sample();
      fclose(f);
    });
  }
}

void Telemetry::ScrapeLoop(int lfd) {
  while (!impl_->scrape_stop.load(std::memory_order_acquire)) {
    struct pollfd pfd = {lfd, POLLIN, 0};
    int pr = ::poll(&pfd, 1, 200);
    if (pr <= 0) continue;
    int cfd = ::accept(lfd, nullptr, nullptr);
    if (cfd < 0) continue;
    // Drain whatever request line arrived. GET /healthz gets a tiny liveness
    // 200 (the serving tier's probe endpoint); every other path gets the
    // exposition — a scraper that sends nothing within the poll window
    // still gets it.
    char reqbuf[1024];
    ssize_t rn = 0;
    struct pollfd cpfd = {cfd, POLLIN, 0};
    if (::poll(&cpfd, 1, 250) > 0) {
      rn = ::recv(cfd, reqbuf, sizeof(reqbuf) - 1, MSG_DONTWAIT);
    }
    if (rn < 0) rn = 0;
    reqbuf[rn] = '\0';
    bool healthz = strncmp(reqbuf, "GET /healthz", 12) == 0;
    std::string body = healthz ? "ok\n" : PrometheusText();
    std::string resp =
        std::string("HTTP/1.1 200 OK\r\nContent-Type: ") +
        (healthz ? "text/plain" : "text/plain; version=0.0.4") +
        "\r\nContent-Length: " + std::to_string(body.size()) +
        "\r\nConnection: close\r\n\r\n" + body;
    (void)!::send(cfd, resp.data(), resp.size(), MSG_NOSIGNAL);
    ::close(cfd);
  }
  ::close(lfd);
}

Telemetry::~Telemetry() { ShutdownForExit(); }

void Telemetry::ShutdownForExit() {
  // Forked child (atexit hooks registered pre-fork still run at its exit()):
  // the pusher/scraper pthreads never existed here and the mutexes below may
  // have been captured locked at fork — skip the shutdown handshake
  // entirely; the parent owns the final flush.
  if (ForkGeneration() != impl_->created_fork_gen) return;
  if (impl_->pusher.joinable() || impl_->ts_sampler.joinable()) {
    {
      MutexLock lk(impl_->push_mu);
      impl_->stopping = true;
    }
    impl_->push_cv.NotifyAll();
    if (impl_->pusher.joinable()) impl_->pusher.join();
    if (impl_->ts_sampler.joinable()) impl_->ts_sampler.join();
  }
  if (impl_->scraper.joinable()) {
    impl_->scrape_stop.store(true, std::memory_order_release);
    impl_->scraper.join();
  }
  FlushTrace();
}

bool Telemetry::SetTraceDir(const std::string& dir) {
  // Flush under the old target first so no buffered span lands in the wrong
  // file (or is lost on disable).
  FlushTrace();
  Impl* im = impl_.get();
  MutexLock lk(im->span_mu);
  if (dir.empty()) {
    trace_enabled_.store(false, std::memory_order_relaxed);
    im->open_spans.clear();
    return true;
  }
  im->trace_path = dir + "/tpunet-trace-rank" + std::to_string(im->rank) + ".json";
  im->trace_header_written = false;
  trace_enabled_.store(true, std::memory_order_relaxed);
  RegisterAtExit();
  return true;
}

void Telemetry::OnRequestStart(uint64_t owner, bool is_send, uint64_t comm, uint64_t req,
                               uint64_t nbytes) {
  Impl* im = impl_.get();
  if (is_send) {
    im->isend_count.fetch_add(1, std::memory_order_relaxed);
    im->isend_bytes.fetch_add(nbytes, std::memory_order_relaxed);
    im->isend_hist[HistBucket(nbytes)].fetch_add(1, std::memory_order_relaxed);
  } else {
    im->irecv_count.fetch_add(1, std::memory_order_relaxed);
    im->irecv_bytes.fetch_add(nbytes, std::memory_order_relaxed);
    im->irecv_hist[HistBucket(nbytes)].fetch_add(1, std::memory_order_relaxed);
  }
  im->inflight.fetch_add(1, std::memory_order_relaxed);
  flightrec::Record(flightrec::Ev::kReqStart, comm, req, nbytes,
                    is_send ? 1u : 0u);
  if (tracing_enabled()) {
    Span s;
    s.kind = Span::Kind::kReq;
    s.is_send = is_send;
    s.comm = comm;
    s.req = req;
    s.nbytes = nbytes;
    s.start_us = NowUs();
    MutexLock lk(im->span_mu);
    im->open_spans[SpanKey{owner, req}] = std::move(s);
  }
}

void Telemetry::OnRequestDone(uint64_t owner, uint64_t req, bool failed) {
  Impl* im = impl_.get();
  // Clamp-to-zero guard: a done for an unseen request must not wrap the gauge.
  uint64_t cur = im->inflight.load(std::memory_order_relaxed);
  while (cur > 0 &&
         !im->inflight.compare_exchange_weak(cur, cur - 1, std::memory_order_relaxed)) {
  }
  if (failed) im->failed.fetch_add(1, std::memory_order_relaxed);
  flightrec::Record(flightrec::Ev::kReqDone, req, 0, 0, failed ? 1u : 0u);
  if (!tracing_enabled()) return;
  bool flush = false;
  {
    MutexLock lk(im->span_mu);
    auto it = im->open_spans.find(SpanKey{owner, req});
    if (it == im->open_spans.end()) return;
    Span s = it->second;
    im->open_spans.erase(it);
    s.dur_us = NowUs() - s.start_us;
    im->done_spans.push_back(std::move(s));
    flush = im->done_spans.size() >= 4096;
  }
  if (flush) FlushTrace();
}

void Telemetry::OnStreamBytes(bool is_send, uint64_t stream_idx, uint64_t nbytes,
                              int cls) {
  if (stream_idx >= kMaxStreamStats) stream_idx = kMaxStreamStats - 1;
  if (cls < 0 || cls >= kQosClassCount) cls = 1;  // unknown class: bulk
  auto& slot = is_send ? impl_->stream_tx[cls][stream_idx]
                       : impl_->stream_rx[cls][stream_idx];
  slot.fetch_add(nbytes, std::memory_order_relaxed);
  flightrec::Record(is_send ? flightrec::Ev::kWireSend : flightrec::Ev::kWireRecv,
                    stream_idx, nbytes, 0, static_cast<uint32_t>(cls));
}

void Telemetry::OnQosQueueWait(int cls, uint64_t wait_us) {
  if (cls < 0 || cls >= kQosClassCount) return;
  impl_->qos_wait[cls].Observe(wait_us);
  flightrec::Record(flightrec::Ev::kQosWait, static_cast<uint64_t>(cls), wait_us);
}

void Telemetry::OnQosPreempt(int cls) {
  if (cls < 0 || cls >= kQosClassCount) return;
  impl_->qos_preempts[cls].fetch_add(1, std::memory_order_relaxed);
  flightrec::Record(flightrec::Ev::kQosPreempt, static_cast<uint64_t>(cls));
}

void Telemetry::MaybeSampleStream(bool is_send, uint64_t stream_idx, int fd) {
  Impl* im = impl_.get();
  if (im->tcp_interval_us == 0 || fd < 0) return;
  if (stream_idx >= kMaxStreamStats) stream_idx = kMaxStreamStats - 1;
  StreamTcpState* slots = is_send ? im->tcp_tx : im->tcp_rx;
  StreamTcpState& slot = slots[stream_idx];
  uint64_t now = NowUs();
  uint64_t due = slot.next_sample_us.load(std::memory_order_relaxed);
  if (now < due) return;
  // One sampler per slot per window: losing the CAS means a sibling thread
  // is already doing this window's getsockopt.
  if (!slot.next_sample_us.compare_exchange_strong(due, now + im->tcp_interval_us,
                                                   std::memory_order_relaxed)) {
    return;
  }
  TcpInfoCompat ti = {};
  socklen_t len = sizeof(ti);
  if (::getsockopt(fd, IPPROTO_TCP, TCP_INFO, &ti, &len) != 0) return;
  if (len < offsetof(TcpInfoCompat, total_retrans) + sizeof(uint32_t)) return;
  uint64_t rtt = ti.rtt;  // µs already
  slot.rtt_us.store(rtt, std::memory_order_relaxed);
  uint64_t old_srtt = slot.srtt_us.load(std::memory_order_relaxed);
  uint64_t srtt = old_srtt == 0 ? rtt : (3 * old_srtt + rtt) / 4;
  slot.srtt_us.store(srtt, std::memory_order_relaxed);
  slot.retrans_total.store(ti.total_retrans, std::memory_order_relaxed);
  slot.cwnd.store(ti.snd_cwnd, std::memory_order_relaxed);
  if (len >= offsetof(TcpInfoCompat, delivery_rate) + sizeof(uint64_t)) {
    slot.delivery_rate_bps.store(ti.delivery_rate * 8, std::memory_order_relaxed);
  }
  if (len >= offsetof(TcpInfoCompat, min_rtt) + sizeof(uint32_t)) {
    slot.min_rtt_us.store(ti.min_rtt, std::memory_order_relaxed);
  }
  slot.sampled.store(1, std::memory_order_relaxed);

  // Straggler check: this stream's smoothed RTT vs the median across the
  // active same-direction streams. Hysteresis (rising edge only) keeps a
  // persistently slow stream from inflating the counter every sample.
  if (srtt < im->straggler_min_rtt_us || im->straggler_factor == 0) {
    slot.straggling.store(0, std::memory_order_relaxed);
    return;
  }
  std::vector<uint64_t> srtts;
  srtts.reserve(kMaxStreamStats);
  for (int i = 0; i < kMaxStreamStats; ++i) {
    if (slots[i].sampled.load(std::memory_order_relaxed)) {
      srtts.push_back(slots[i].srtt_us.load(std::memory_order_relaxed));
    }
  }
  if (srtts.size() < 2) return;
  std::nth_element(srtts.begin(), srtts.begin() + srtts.size() / 2, srtts.end());
  uint64_t median = srtts[srtts.size() / 2];
  if (median > 0 && srtt > im->straggler_factor * median) {
    if (!slot.straggling.exchange(1, std::memory_order_relaxed)) {
      im->straggler_events.fetch_add(1, std::memory_order_relaxed);
      if (tracing_enabled()) {
        Span s;
        s.kind = Span::Kind::kInstant;
        s.is_send = is_send;
        s.comm = stream_idx;
        s.req = srtt;
        s.nbytes = median;
        s.start_us = now;
        s.name = "straggler-stream" + std::to_string(stream_idx);
        MutexLock lk(im->span_mu);
        im->done_spans.push_back(std::move(s));
      }
    }
  } else {
    slot.straggling.store(0, std::memory_order_relaxed);
  }
}

bool Telemetry::StreamStraggling(bool is_send, uint64_t stream_idx) const {
  if (stream_idx >= kMaxStreamStats) stream_idx = kMaxStreamStats - 1;
  const StreamTcpState* slots = is_send ? impl_->tcp_tx : impl_->tcp_rx;
  return slots[stream_idx].straggling.load(std::memory_order_relaxed) != 0;
}

void Telemetry::OnLaneWeight(uint64_t lane, uint64_t weight) {
  if (lane >= kMaxStreamStats) lane = kMaxStreamStats - 1;
  impl_->lane_weight[lane].store(weight, std::memory_order_relaxed);
}

void Telemetry::OnLaneRate(uint64_t lane, uint64_t bps) {
  if (lane >= kMaxStreamStats) lane = kMaxStreamStats - 1;
  impl_->lane_rate_bps[lane].store(bps, std::memory_order_relaxed);
}

void Telemetry::OnLaneBytes(bool is_send, uint64_t lane, uint64_t nbytes) {
  if (lane >= kMaxStreamStats) lane = kMaxStreamStats - 1;
  impl_->lane_bytes[lane][is_send ? 0 : 1].fetch_add(nbytes,
                                                     std::memory_order_relaxed);
}

void Telemetry::OnRestripe() {
  impl_->restripe_events.fetch_add(1, std::memory_order_relaxed);
  flightrec::Record(flightrec::Ev::kRestripe, 0);
}

void Telemetry::OnShmBytes(bool is_send, uint64_t nbytes) {
  impl_->shm_bytes[is_send ? 0 : 1].fetch_add(nbytes, std::memory_order_relaxed);
}

void Telemetry::OnShmWakeup() {
  impl_->shm_wakeups.fetch_add(1, std::memory_order_relaxed);
}

void Telemetry::OnShmReduceBytes(uint64_t nbytes) {
  impl_->shm_reduce_bytes.fetch_add(nbytes, std::memory_order_relaxed);
}

namespace {
thread_local uint64_t t_consumed_first_wire_us = 0;
}  // namespace

uint64_t Telemetry::TakeConsumedFirstWireUs() {
  uint64_t v = t_consumed_first_wire_us;
  t_consumed_first_wire_us = 0;
  return v;
}

void Telemetry::OnRequestStages(uint64_t post_us, uint64_t first_wire_us,
                                uint64_t last_wire_us) {
  t_consumed_first_wire_us = first_wire_us;
  if (post_us == 0) return;  // engine predates stamping / synthetic request
  Impl* im = impl_.get();
  uint64_t done_us = NowUs();
  if (done_us < post_us) return;
  im->req_total.Observe(done_us - post_us);
  if (last_wire_us == 0) return;  // zero-byte message: no wire stage
  if (first_wire_us == 0 || first_wire_us < post_us) first_wire_us = last_wire_us;
  if (first_wire_us < post_us || last_wire_us < first_wire_us) return;
  im->req_queue.Observe(first_wire_us - post_us);
  im->req_wire.Observe(last_wire_us - first_wire_us);
}

void Telemetry::OnCollPhase(uint64_t comm_id, uint64_t coll_seq, const char* phase,
                            uint64_t start_us, uint64_t dur_us, uint64_t nbytes) {
  if (!tracing_enabled()) return;
  Impl* im = impl_.get();
  Span s;
  s.kind = Span::Kind::kColl;
  s.comm = comm_id;
  s.req = coll_seq;
  s.nbytes = nbytes;
  s.start_us = start_us;
  s.dur_us = dur_us;
  s.name = phase;
  bool flush = false;
  {
    MutexLock lk(im->span_mu);
    im->done_spans.push_back(std::move(s));
    flush = im->done_spans.size() >= 4096;
  }
  if (flush) FlushTrace();
}

void Telemetry::OnCollPart(const char* part, uint64_t comm_id, uint64_t coll_seq,
                           const char* phase, const char* dir, uint64_t start_us,
                           uint64_t dur_us) {
  if (!tracing_enabled()) return;
  Impl* im = impl_.get();
  Span s;
  s.kind = Span::Kind::kPart;
  s.comm = comm_id;
  s.req = coll_seq;
  s.start_us = start_us;
  s.dur_us = dur_us;
  s.name = part;
  s.extra = std::string("\"phase\":\"") + phase + "\"";
  if (dir) s.extra += std::string(",\"dir\":\"") + dir + "\"";
  bool flush = false;
  {
    MutexLock lk(im->span_mu);
    im->done_spans.push_back(std::move(s));
    flush = im->done_spans.size() >= 4096;
  }
  if (flush) FlushTrace();
}

bool Telemetry::OnProgramSpan(const char* name, uint64_t start_us, uint64_t dur_us,
                              uint64_t seq, uint64_t nbytes, const char* parent,
                              const char* kind, int64_t step, int64_t chunk) {
  if (!tracing_enabled()) return false;
  Impl* im = impl_.get();
  Span s;
  s.kind = Span::Kind::kProg;
  // One Perfetto track per calling thread (the io_callback body runs on a
  // runtime thread, fit() on the caller's); folded so merge_traces()'s
  // rank * 1e6 + tid stays inside the rank's range.
  s.comm = static_cast<uint64_t>(::syscall(SYS_gettid)) % 1000000;
  s.req = seq;
  s.nbytes = nbytes;
  s.start_us = start_us;
  s.dur_us = dur_us;
  s.name = name;
  if (parent && *parent) s.extra += std::string(",\"parent\":\"") + parent + "\"";
  if (kind && *kind) s.extra += std::string(",\"kind\":\"") + kind + "\"";
  if (step >= 0) s.extra += ",\"step\":" + std::to_string(step);
  if (chunk >= 0) s.extra += ",\"chunk\":" + std::to_string(chunk);
  bool flush = false;
  {
    MutexLock lk(im->span_mu);
    im->done_spans.push_back(std::move(s));
    flush = im->done_spans.size() >= 4096;
  }
  if (flush) FlushTrace();
  return true;
}

void Telemetry::OnBridgeCall(int kind, uint64_t nbytes) {
  if (kind < 0 || kind >= kBridgeKindCount) return;
  impl_->bridge_calls[kind].fetch_add(1, std::memory_order_relaxed);
  impl_->bridge_bytes[kind].fetch_add(nbytes, std::memory_order_relaxed);
}

void Telemetry::OnBridgeChunks(int kind, uint64_t chunks, uint64_t in_flight) {
  if (kind < 0 || kind >= kBridgeKindCount) return;
  impl_->bridge_chunks[kind].fetch_add(chunks, std::memory_order_relaxed);
  auto& deepest = impl_->bridge_chunks_in_flight_max[kind];
  uint64_t seen = deepest.load(std::memory_order_relaxed);
  while (seen < in_flight &&
         !deepest.compare_exchange_weak(seen, in_flight, std::memory_order_relaxed)) {
  }
}

void Telemetry::OnBridgeMinorFaults(int kind, uint64_t faults) {
  if (kind < 0 || kind >= kBridgeKindCount) return;
  impl_->bridge_minor_faults[kind].fetch_add(faults, std::memory_order_relaxed);
}

void Telemetry::OnFaultInjected(int action) {
  if (action < 0 || action >= kFaultActionSlots) return;
  impl_->faults_injected[action].fetch_add(1, std::memory_order_relaxed);
  flightrec::Record(flightrec::Ev::kFault, static_cast<uint64_t>(action));
}

void Telemetry::OnStreamFailover() {
  impl_->stream_failovers.fetch_add(1, std::memory_order_relaxed);
  flightrec::Record(flightrec::Ev::kFailover, 0);
}

void Telemetry::OnCrcError() {
  impl_->crc_errors.fetch_add(1, std::memory_order_relaxed);
  flightrec::Record(flightrec::Ev::kCrcError, 0);
}

void Telemetry::OnServeLatency(int kind, uint64_t us) {
  if (kind == 0) {
    impl_->req_ttft.Observe(us);
  } else if (kind == 1) {
    impl_->req_tpot.Observe(us);
  }
}

void Telemetry::OnServeQueueDepth(int tier, uint64_t depth) {
  if (tier < 0 || tier >= kServeTierCount) return;
  impl_->serve_depth[tier].store(depth, std::memory_order_relaxed);
}

void Telemetry::OnRewirePhase(int phase, uint64_t us) {
  if (phase < 0 || phase >= kRewirePhaseCount) return;
  impl_->rewire_phase[phase].Observe(us);
  flightrec::Record(flightrec::Ev::kRewirePhase, static_cast<uint64_t>(phase), us);
}

void Telemetry::OnChurnEvent(int kind) {
  if (kind < 0 || kind >= kChurnKindCount) return;
  impl_->churn_events[kind].fetch_add(1, std::memory_order_relaxed);
}

void Telemetry::OnWorldSize(uint64_t world) {
  impl_->world_size.store(world, std::memory_order_relaxed);
}

void Telemetry::OnSwapPhase(int phase, uint64_t us) {
  if (phase < 0 || phase >= kSwapPhaseCount) return;
  impl_->swap_phase[phase].Observe(us);
  flightrec::Record(flightrec::Ev::kSwapPhase, static_cast<uint64_t>(phase), us);
}

void Telemetry::OnSwapEvent(int kind) {
  if (kind < 0 || kind >= kSwapKindCount) return;
  impl_->swap_events[kind].fetch_add(1, std::memory_order_relaxed);
}

void Telemetry::OnWeightVersion(uint64_t version) {
  impl_->weight_version.store(version, std::memory_order_relaxed);
}

int Telemetry::MetricsPort() const {
  return impl_->scrape_bound_port.load(std::memory_order_acquire);
}

void Telemetry::Reset() {
  Impl* im = impl_.get();
  im->isend_count.store(0, std::memory_order_relaxed);
  im->irecv_count.store(0, std::memory_order_relaxed);
  im->isend_bytes.store(0, std::memory_order_relaxed);
  im->irecv_bytes.store(0, std::memory_order_relaxed);
  for (int i = 0; i < kHistBuckets; ++i) {
    im->isend_hist[i].store(0, std::memory_order_relaxed);
    im->irecv_hist[i].store(0, std::memory_order_relaxed);
  }
  // inflight is deliberately NOT reset: it tracks live requests whose done
  // events will still arrive — zeroing it would make them wrap the clamp.
  im->failed.store(0, std::memory_order_relaxed);
  for (int c = 0; c < kQosClassCount; ++c) {
    for (int i = 0; i < kMaxStreamStats; ++i) {
      im->stream_tx[c][i].store(0, std::memory_order_relaxed);
      im->stream_rx[c][i].store(0, std::memory_order_relaxed);
    }
    im->qos_wait[c].Reset();
    im->qos_preempts[c].store(0, std::memory_order_relaxed);
  }
  for (int i = 0; i < kMaxStreamStats; ++i) {
    for (StreamTcpState* slots : {im->tcp_tx, im->tcp_rx}) {
      slots[i].rtt_us.store(0, std::memory_order_relaxed);
      slots[i].srtt_us.store(0, std::memory_order_relaxed);
      slots[i].retrans_total.store(0, std::memory_order_relaxed);
      slots[i].cwnd.store(0, std::memory_order_relaxed);
      slots[i].delivery_rate_bps.store(0, std::memory_order_relaxed);
      slots[i].min_rtt_us.store(0, std::memory_order_relaxed);
      slots[i].sampled.store(0, std::memory_order_relaxed);
      slots[i].straggling.store(0, std::memory_order_relaxed);
      slots[i].next_sample_us.store(0, std::memory_order_relaxed);
    }
    im->lane_weight[i].store(0, std::memory_order_relaxed);
    im->lane_rate_bps[i].store(0, std::memory_order_relaxed);
    im->lane_bytes[i][0].store(0, std::memory_order_relaxed);
    im->lane_bytes[i][1].store(0, std::memory_order_relaxed);
  }
  im->restripe_events.store(0, std::memory_order_relaxed);
  im->shm_bytes[0].store(0, std::memory_order_relaxed);
  im->shm_bytes[1].store(0, std::memory_order_relaxed);
  im->shm_wakeups.store(0, std::memory_order_relaxed);
  im->shm_reduce_bytes.store(0, std::memory_order_relaxed);
  for (int i = 0; i < kFaultActionSlots; ++i) {
    im->faults_injected[i].store(0, std::memory_order_relaxed);
  }
  im->stream_failovers.store(0, std::memory_order_relaxed);
  im->crc_errors.store(0, std::memory_order_relaxed);
  im->straggler_events.store(0, std::memory_order_relaxed);
  ResetIoSyscallCounts();
  ResetReduceBytesTotal();
  ResetCodecBytesTotals();
  ResetCollDispatchCounters();
  im->req_queue.Reset();
  im->req_wire.Reset();
  im->req_total.Reset();
  im->req_ttft.Reset();
  im->req_tpot.Reset();
  for (auto& d : im->serve_depth) d.store(0, std::memory_order_relaxed);
  for (auto& h : im->rewire_phase) h.Reset();
  for (auto& c : im->churn_events) c.store(0, std::memory_order_relaxed);
  im->world_size.store(0, std::memory_order_relaxed);
  for (auto& h : im->swap_phase) h.Reset();
  for (auto& c : im->swap_events) c.store(0, std::memory_order_relaxed);
  im->weight_version.store(0, std::memory_order_relaxed);
  for (auto& c : im->bridge_calls) c.store(0, std::memory_order_relaxed);
  for (auto& c : im->bridge_bytes) c.store(0, std::memory_order_relaxed);
  for (auto& c : im->bridge_chunks) c.store(0, std::memory_order_relaxed);
  for (auto& c : im->bridge_chunks_in_flight_max) c.store(0, std::memory_order_relaxed);
  for (auto& c : im->bridge_minor_faults) c.store(0, std::memory_order_relaxed);
  {
    MutexLock lk(im->win_mu);
    im->win_init = false;
    im->win_last_us = 0;
    memset(im->win_tx, 0, sizeof(im->win_tx));
    memset(im->win_rx, 0, sizeof(im->win_rx));
    for (int c = 0; c < kQosClassCount; ++c) {
      im->fair_tx_bits[c].store(DoubleToBits(1.0), std::memory_order_relaxed);
      im->fair_rx_bits[c].store(DoubleToBits(1.0), std::memory_order_relaxed);
    }
  }
  im->start_us.store(NowUs(), std::memory_order_relaxed);
}

MetricsSnapshot Telemetry::Snapshot() const {
  Impl* im = impl_.get();
  MetricsSnapshot s;
  uint64_t cls_tx[kQosClassCount][kMaxStreamStats];
  uint64_t cls_rx[kQosClassCount][kMaxStreamStats];
  for (int c = 0; c < kQosClassCount; ++c) {
    for (int i = 0; i < kMaxStreamStats; ++i) {
      cls_tx[c][i] = im->stream_tx[c][i].load(std::memory_order_relaxed);
      cls_rx[c][i] = im->stream_rx[c][i].load(std::memory_order_relaxed);
      s.stream_tx_bytes[i] += cls_tx[c][i];
      s.stream_rx_bytes[i] += cls_rx[c][i];
      s.qos_bytes[c][0] += cls_tx[c][i];
      s.qos_bytes[c][1] += cls_rx[c][i];
    }
    im->qos_wait[c].SnapshotInto(&s.qos_wait_us[c]);
    s.qos_preempts[c] = im->qos_preempts[c].load(std::memory_order_relaxed);
  }
  // Fairness window roll: at most once per TPUNET_FAIRNESS_WINDOW_MS so two
  // back-to-back scrapes don't compute Jain over an empty delta. The first
  // roll covers everything since start/Reset. Each traffic class rolls its
  // OWN per-stream deltas: the gauge answers "is striping fair WITHIN this
  // class" — cross-class weighting is the scheduler's job, not skew.
  {
    MutexLock lk(im->win_mu);
    uint64_t now = NowUs();
    if (!im->win_init || now - im->win_last_us >= im->fairness_window_us) {
      bool moved_any = false;
      for (int c = 0; c < kQosClassCount; ++c) {
        uint64_t dtx[kMaxStreamStats], drx[kMaxStreamStats];
        uint64_t tot_tx = 0, tot_rx = 0;
        for (int i = 0; i < kMaxStreamStats; ++i) {
          dtx[i] = cls_tx[c][i] - im->win_tx[c][i];
          drx[i] = cls_rx[c][i] - im->win_rx[c][i];
          tot_tx += dtx[i];
          tot_rx += drx[i];
        }
        // Only move the gauge when bytes moved (else keep the last verdict).
        if (tot_tx > 0) {
          im->fair_tx_bits[c].store(
              DoubleToBits(JainIndex(dtx, kMaxStreamStats)),
              std::memory_order_relaxed);
        }
        if (tot_rx > 0) {
          im->fair_rx_bits[c].store(
              DoubleToBits(JainIndex(drx, kMaxStreamStats)),
              std::memory_order_relaxed);
        }
        if (tot_tx > 0 || tot_rx > 0) {
          memcpy(im->win_tx[c], cls_tx[c], sizeof(im->win_tx[c]));
          memcpy(im->win_rx[c], cls_rx[c], sizeof(im->win_rx[c]));
          moved_any = true;
        }
      }
      if (!im->win_init || moved_any) {
        if (!im->win_init) {
          memcpy(im->win_tx, cls_tx, sizeof(im->win_tx));
          memcpy(im->win_rx, cls_rx, sizeof(im->win_rx));
        }
        im->win_init = true;
        im->win_last_us = now;
      }
    }
  }
  for (int c = 0; c < kQosClassCount; ++c) {
    s.fairness_tx[c] =
        BitsToDouble(im->fair_tx_bits[c].load(std::memory_order_relaxed));
    s.fairness_rx[c] =
        BitsToDouble(im->fair_rx_bits[c].load(std::memory_order_relaxed));
  }
  for (int i = 0; i < kMaxStreamStats; ++i) {
    for (auto [slots, out] : {std::pair<StreamTcpState*, StreamTcpSample*>{
                                  im->tcp_tx, s.stream_tcp_tx},
                              {im->tcp_rx, s.stream_tcp_rx}}) {
      out[i].sampled = slots[i].sampled.load(std::memory_order_relaxed) != 0;
      out[i].rtt_us = slots[i].rtt_us.load(std::memory_order_relaxed);
      out[i].srtt_us = slots[i].srtt_us.load(std::memory_order_relaxed);
      out[i].retrans_total = slots[i].retrans_total.load(std::memory_order_relaxed);
      out[i].cwnd = slots[i].cwnd.load(std::memory_order_relaxed);
      out[i].delivery_rate_bps =
          slots[i].delivery_rate_bps.load(std::memory_order_relaxed);
      out[i].min_rtt_us = slots[i].min_rtt_us.load(std::memory_order_relaxed);
    }
    s.lane_weight[i] = im->lane_weight[i].load(std::memory_order_relaxed);
    s.lane_rate_bps[i] = im->lane_rate_bps[i].load(std::memory_order_relaxed);
    s.lane_bytes[i][0] = im->lane_bytes[i][0].load(std::memory_order_relaxed);
    s.lane_bytes[i][1] = im->lane_bytes[i][1].load(std::memory_order_relaxed);
  }
  s.restripe_events = im->restripe_events.load(std::memory_order_relaxed);
  s.shm_bytes[0] = im->shm_bytes[0].load(std::memory_order_relaxed);
  s.shm_bytes[1] = im->shm_bytes[1].load(std::memory_order_relaxed);
  s.shm_wakeups = im->shm_wakeups.load(std::memory_order_relaxed);
  s.shm_reduce_bytes = im->shm_reduce_bytes.load(std::memory_order_relaxed);
  s.straggler_events = im->straggler_events.load(std::memory_order_relaxed);
  s.isend_count = im->isend_count.load(std::memory_order_relaxed);
  s.irecv_count = im->irecv_count.load(std::memory_order_relaxed);
  s.isend_bytes = im->isend_bytes.load(std::memory_order_relaxed);
  s.irecv_bytes = im->irecv_bytes.load(std::memory_order_relaxed);
  for (int i = 0; i < kHistBuckets; ++i) {
    s.isend_hist[i] = im->isend_hist[i].load(std::memory_order_relaxed);
    s.irecv_hist[i] = im->irecv_hist[i].load(std::memory_order_relaxed);
  }
  s.inflight = im->inflight.load(std::memory_order_relaxed);
  s.failed_requests = im->failed.load(std::memory_order_relaxed);
  for (int i = 0; i < kFaultActionSlots; ++i) {
    s.faults_injected[i] = im->faults_injected[i].load(std::memory_order_relaxed);
  }
  s.stream_failovers = im->stream_failovers.load(std::memory_order_relaxed);
  s.crc_errors = im->crc_errors.load(std::memory_order_relaxed);
  im->req_queue.SnapshotInto(&s.req_queue_us);
  im->req_wire.SnapshotInto(&s.req_wire_us);
  im->req_total.SnapshotInto(&s.req_total_us);
  im->req_ttft.SnapshotInto(&s.req_ttft_us);
  im->req_tpot.SnapshotInto(&s.req_tpot_us);
  for (int p = 0; p < kRewirePhaseCount; ++p) {
    im->rewire_phase[p].SnapshotInto(&s.rewire_us[p]);
  }
  for (int k = 0; k < kChurnKindCount; ++k) {
    s.churn_events[k] = im->churn_events[k].load(std::memory_order_relaxed);
  }
  s.world_size = im->world_size.load(std::memory_order_relaxed);
  for (int p = 0; p < kSwapPhaseCount; ++p) {
    im->swap_phase[p].SnapshotInto(&s.swap_us[p]);
  }
  for (int k = 0; k < kSwapKindCount; ++k) {
    s.swap_events[k] = im->swap_events[k].load(std::memory_order_relaxed);
  }
  for (int k = 0; k < kBridgeKindCount; ++k) {
    s.bridge_calls[k] = im->bridge_calls[k].load(std::memory_order_relaxed);
    s.bridge_bytes[k] = im->bridge_bytes[k].load(std::memory_order_relaxed);
    s.bridge_chunks[k] = im->bridge_chunks[k].load(std::memory_order_relaxed);
    s.bridge_chunks_in_flight_max[k] =
        im->bridge_chunks_in_flight_max[k].load(std::memory_order_relaxed);
    s.bridge_minor_faults[k] =
        im->bridge_minor_faults[k].load(std::memory_order_relaxed);
  }
  s.weight_version = im->weight_version.load(std::memory_order_relaxed);
  for (int t = 0; t < kServeTierCount; ++t) {
    s.serve_queue_depth[t] = im->serve_depth[t].load(std::memory_order_relaxed);
  }
  for (int i = 0; i < kIoOpCount; ++i) {
    s.engine_syscalls[i] = IoSyscallCount(static_cast<IoOp>(i));
  }
  s.reduce_bytes = ReduceBytesTotal();
  for (int c = 0; c < 2; ++c) {
    for (int d = 0; d < 2; ++d) {
      // Snapshot slot c maps to WireCodec c+1 (kF32 passthrough is uncounted).
      s.codec_bytes[c][d] = CodecBytesTotal(static_cast<WireCodec>(c + 1), d);
    }
  }
  for (int d = 0; d < 2; ++d) s.codec_payload_bytes[d] = CodecPayloadBytesTotal(d);
  for (int a = 0; a < 3; ++a) {
    // Snapshot slot a maps to CollAlgo a+1 (kAuto never executes a step).
    s.coll_steps[a] = CollStepsTotal(static_cast<CollAlgo>(a + 1));
  }
  // Hierarchical schedules: their stages count separately (slots 3/4 =
  // hier.intra/hier.inter, 5/6 = a2a.intra/a2a.inter) — the DCN-round
  // shrinkage IS the claim.
  s.coll_steps[3] = HierStepsTotal(false);
  s.coll_steps[4] = HierStepsTotal(true);
  s.coll_steps[5] = A2aStepsTotal(false);
  s.coll_steps[6] = A2aStepsTotal(true);
  for (int a = 0; a < 6; ++a) {
    for (int k = 0; k < kCollKindCount; ++k) {
      s.coll_algo_selected[k][a] =
          CollAlgoSelectedTotal(static_cast<CollKind>(k), static_cast<CollAlgo>(a + 1));
    }
  }
  for (int st = 0; st < kA2aStageCount; ++st) {
    for (int d = 0; d < 2; ++d) s.a2a_bytes[st][d] = A2aBytesTotal(st, d);
  }
  s.uptime_s = (NowUs() - im->start_us.load(std::memory_order_relaxed)) / 1e6;
  return s;
}

std::string Telemetry::PrometheusText() const {
  MetricsSnapshot s = Snapshot();
  char buf[2048];
  std::string out;
  auto emit = [&](const char* fmt, auto... args) {
    snprintf(buf, sizeof(buf), fmt, args...);
    out += buf;
  };
  // One # HELP + # TYPE header per family, immediately before its samples,
  // so the exposition passes a Prometheus text-format lint.
  auto family = [&](const char* name, const char* type, const char* help) {
    emit("# HELP %s %s\n# TYPE %s %s\n", name, help, name, type);
  };
  int64_t rank = impl_->rank;
  // Instrument names follow the reference (isend_nbytes / irecv_nbytes value
  // recorders nthread:172-180, bytes/s observers :343-348, hold_on_request
  // in-flight gauge tokio:184-190).
  auto size_hist = [&](const char* name, const char* help, const uint64_t* hist,
                       uint64_t sum, uint64_t count) {
    family(name, "histogram", help);
    uint64_t cum = 0;
    for (int i = 0; i < kHistBuckets - 1; ++i) {
      cum += hist[i];
      emit("%s_bucket{rank=\"%lld\",le=\"%llu\"} %llu\n", name, (long long)rank,
           (unsigned long long)kHistBounds[i], (unsigned long long)cum);
    }
    cum += hist[kHistBuckets - 1];
    emit("%s_bucket{rank=\"%lld\",le=\"+Inf\"} %llu\n", name, (long long)rank,
         (unsigned long long)cum);
    emit("%s_sum{rank=\"%lld\"} %llu\n", name, (long long)rank, (unsigned long long)sum);
    emit("%s_count{rank=\"%lld\"} %llu\n", name, (long long)rank,
         (unsigned long long)count);
  };
  size_hist("tpunet_isend_nbytes", "Posted isend message sizes in bytes.",
            s.isend_hist, s.isend_bytes, s.isend_count);
  size_hist("tpunet_irecv_nbytes", "Posted irecv message sizes in bytes.",
            s.irecv_hist, s.irecv_bytes, s.irecv_count);
  family("tpunet_isend_nbytes_per_second", "gauge",
         "Mean outbound payload rate since start (bytes/s).");
  emit("tpunet_isend_nbytes_per_second{rank=\"%lld\"} %.1f\n", (long long)rank,
       s.uptime_s > 0 ? s.isend_bytes / s.uptime_s : 0.0);
  family("tpunet_irecv_nbytes_per_second", "gauge",
         "Mean inbound payload rate since start (bytes/s).");
  emit("tpunet_irecv_nbytes_per_second{rank=\"%lld\"} %.1f\n", (long long)rank,
       s.uptime_s > 0 ? s.irecv_bytes / s.uptime_s : 0.0);
  family("tpunet_stream_tx_bytes", "counter",
         "Payload bytes sent per data-stream index (all comms aggregated).");
  for (int i = 0; i < kMaxStreamStats; ++i) {
    if (s.stream_tx_bytes[i] == 0) continue;
    emit("tpunet_stream_tx_bytes{rank=\"%lld\",stream=\"%d\"} %llu\n", (long long)rank, i,
         (unsigned long long)s.stream_tx_bytes[i]);
  }
  family("tpunet_stream_rx_bytes", "counter",
         "Payload bytes received per data-stream index (all comms aggregated).");
  for (int i = 0; i < kMaxStreamStats; ++i) {
    if (s.stream_rx_bytes[i] == 0) continue;
    emit("tpunet_stream_rx_bytes{rank=\"%lld\",stream=\"%d\"} %llu\n", (long long)rank, i,
         (unsigned long long)s.stream_rx_bytes[i]);
  }
  // Per-stream TCP introspection gauges (TCP_INFO sampler). Only sampled
  // slots are emitted; dir distinguishes the send-side and recv-side sockets
  // of the same stream index.
  struct TcpGaugeDef {
    const char* name;
    const char* type;
    const char* help;
    uint64_t StreamTcpSample::*field;
  };
  static const TcpGaugeDef kTcpGauges[] = {
      {"tpunet_stream_rtt_us", "gauge",
       "Last-sampled TCP round-trip time per data stream (tcpi_rtt, microseconds).",
       &StreamTcpSample::rtt_us},
      {"tpunet_stream_retrans_total", "counter",
       "TCP retransmitted segments of the last-sampled socket per data stream "
       "(tcpi_total_retrans).",
       &StreamTcpSample::retrans_total},
      {"tpunet_stream_cwnd", "gauge",
       "TCP congestion window per data stream (tcpi_snd_cwnd, segments).",
       &StreamTcpSample::cwnd},
      {"tpunet_stream_delivery_rate_bps", "gauge",
       "TCP delivery rate per data stream (tcpi_delivery_rate, bits/s; 0 on old kernels).",
       &StreamTcpSample::delivery_rate_bps},
      {"tpunet_stream_min_rtt_us", "gauge",
       "TCP minimum observed round-trip time per data stream (tcpi_min_rtt, "
       "microseconds; 0 on old kernels) — the per-path RTT floor the "
       "straggler detector's static TPUNET_STRAGGLER_MIN_RTT_US knob "
       "approximates.",
       &StreamTcpSample::min_rtt_us},
  };
  for (const TcpGaugeDef& g : kTcpGauges) {
    family(g.name, g.type, g.help);
    for (auto [samples, dir] : {std::pair<const StreamTcpSample*, const char*>{
                                    s.stream_tcp_tx, "tx"},
                                {s.stream_tcp_rx, "rx"}}) {
      for (int i = 0; i < kMaxStreamStats; ++i) {
        if (!samples[i].sampled) continue;
        emit("%s{rank=\"%lld\",stream=\"%d\",dir=\"%s\"} %llu\n", g.name,
             (long long)rank, i, dir, (unsigned long long)(samples[i].*(g.field)));
      }
    }
  }
  static const char* kQosClassNames[kQosClassCount] = {"latency", "bulk",
                                                       "control"};
  family("tpunet_stream_fairness_jain", "gauge",
         "Jain's fairness index over windowed per-stream bytes, per traffic "
         "class (1.0 = perfectly fair striping within the class).");
  for (int c = 0; c < kQosClassCount; ++c) {
    emit("tpunet_stream_fairness_jain{rank=\"%lld\",dir=\"tx\",class=\"%s\"} %.6f\n",
         (long long)rank, kQosClassNames[c], s.fairness_tx[c]);
    emit("tpunet_stream_fairness_jain{rank=\"%lld\",dir=\"rx\",class=\"%s\"} %.6f\n",
         (long long)rank, kQosClassNames[c], s.fairness_rx[c]);
  }
  // QoS families (docs/DESIGN.md "Transport QoS"). Every class x dir series
  // emits even at zero so the two-tenant bench/smoke never look up a
  // missing series.
  family("tpunet_qos_bytes_total", "counter",
         "Payload bytes moved per traffic class and direction (receivers "
         "learn the class from the preamble nibble).");
  for (int c = 0; c < kQosClassCount; ++c) {
    emit("tpunet_qos_bytes_total{rank=\"%lld\",class=\"%s\",dir=\"tx\"} %llu\n",
         (long long)rank, kQosClassNames[c],
         (unsigned long long)s.qos_bytes[c][0]);
    emit("tpunet_qos_bytes_total{rank=\"%lld\",class=\"%s\",dir=\"rx\"} %llu\n",
         (long long)rank, kQosClassNames[c],
         (unsigned long long)s.qos_bytes[c][1]);
  }
  family("tpunet_qos_queue_wait_us", "histogram",
         "Time data chunks waited for QoS wire credit in the DRR scheduler, "
         "per traffic class (microseconds; empty when no wire window is "
         "configured).");
  for (int c = 0; c < kQosClassCount; ++c) {
    const StageHist& h = s.qos_wait_us[c];
    uint64_t cum = 0;
    for (int i = 0; i < kStageHistBuckets - 1; ++i) {
      cum += h.buckets[i];
      emit("tpunet_qos_queue_wait_us_bucket{rank=\"%lld\",class=\"%s\",le=\"%llu\"} %llu\n",
           (long long)rank, kQosClassNames[c],
           (unsigned long long)kStageHistBounds[i], (unsigned long long)cum);
    }
    cum += h.buckets[kStageHistBuckets - 1];
    emit("tpunet_qos_queue_wait_us_bucket{rank=\"%lld\",class=\"%s\",le=\"+Inf\"} %llu\n",
         (long long)rank, kQosClassNames[c], (unsigned long long)cum);
    emit("tpunet_qos_queue_wait_us_sum{rank=\"%lld\",class=\"%s\"} %llu\n",
         (long long)rank, kQosClassNames[c], (unsigned long long)h.sum_us);
    emit("tpunet_qos_queue_wait_us_count{rank=\"%lld\",class=\"%s\"} %llu\n",
         (long long)rank, kQosClassNames[c], (unsigned long long)h.count);
  }
  family("tpunet_qos_preempts_total", "counter",
         "QoS wire-credit grants that jumped ahead of an older waiter of "
         "another class (strict control priority / DRR weighting at work).");
  for (int c = 0; c < kQosClassCount; ++c) {
    emit("tpunet_qos_preempts_total{rank=\"%lld\",class=\"%s\"} %llu\n",
         (long long)rank, kQosClassNames[c],
         (unsigned long long)s.qos_preempts[c]);
  }
  family("tpunet_straggler_events_total", "counter",
         "Streams whose smoothed RTT newly exceeded k x the comm median "
         "(TPUNET_STRAGGLER_FACTOR).");
  emit("tpunet_straggler_events_total{rank=\"%lld\"} %llu\n", (long long)rank,
       (unsigned long long)s.straggler_events);
  // Lane-striping families (docs/DESIGN.md "Lanes & adaptive striping").
  // Gauges emit only for lanes a lane-mode comm has reported (weight floor
  // is 1, so weight 0 means "slot never used"); the bytes counter emits
  // only nonzero cells like the per-stream byte counters.
  family("tpunet_lane_weight", "gauge",
         "Current stripe weight per lane in the weighted chunk scheduler "
         "(TPUNET_LANES; floor 1, demoted lanes decay toward it).");
  for (int i = 0; i < kMaxStreamStats; ++i) {
    if (s.lane_weight[i] == 0) continue;
    emit("tpunet_lane_weight{rank=\"%lld\",lane=\"%d\"} %llu\n", (long long)rank, i,
         (unsigned long long)s.lane_weight[i]);
  }
  family("tpunet_lane_rate_bps", "gauge",
         "Measured per-lane delivery rate the stripe weights chase (EWMA of "
         "payload bytes over wire-service time, bits/s).");
  for (int i = 0; i < kMaxStreamStats; ++i) {
    if (s.lane_rate_bps[i] == 0) continue;
    emit("tpunet_lane_rate_bps{rank=\"%lld\",lane=\"%d\"} %llu\n", (long long)rank, i,
         (unsigned long long)s.lane_rate_bps[i]);
  }
  family("tpunet_lane_bytes_total", "counter",
         "Payload bytes moved per lane and direction on lane-mode comms "
         "(the byte-share convergence signal).");
  for (int d = 0; d < 2; ++d) {
    for (int i = 0; i < kMaxStreamStats; ++i) {
      if (s.lane_bytes[i][d] == 0) continue;
      emit("tpunet_lane_bytes_total{rank=\"%lld\",lane=\"%d\",dir=\"%s\"} %llu\n",
           (long long)rank, i, d == 0 ? "tx" : "rx",
           (unsigned long long)s.lane_bytes[i][d]);
    }
  }
  family("tpunet_restripe_events_total", "counter",
         "Weight-vector epochs published by the adaptive stripe scheduler "
         "(each re-stripes subsequent messages on both sides).");
  emit("tpunet_restripe_events_total{rank=\"%lld\"} %llu\n", (long long)rank,
       (unsigned long long)s.restripe_events);
  // Intra-host SHM transport families (docs/DESIGN.md "Intra-host shared
  // memory"). Both dir series emit even at zero so the shm smoke lane can
  // assert "TCP moved, SHM did not" (and vice versa) without missing-series
  // special cases.
  family("tpunet_shm_bytes_total", "counter",
         "Payload bytes moved through intra-host shared-memory ring "
         "segments, by direction (TPUNET_SHM=1; never counted into the TCP "
         "stream/QoS byte families).");
  emit("tpunet_shm_bytes_total{rank=\"%lld\",dir=\"tx\"} %llu\n", (long long)rank,
       (unsigned long long)s.shm_bytes[0]);
  emit("tpunet_shm_bytes_total{rank=\"%lld\",dir=\"rx\"} %llu\n", (long long)rank,
       (unsigned long long)s.shm_bytes[1]);
  family("tpunet_shm_wakeups_total", "counter",
         "Futex wake syscalls issued by the SHM ring protocol (bytes/wakeup "
         "is the ring's syscalls/MiB analogue — steady-state streaming "
         "should wake rarely).");
  emit("tpunet_shm_wakeups_total{rank=\"%lld\"} %llu\n", (long long)rank,
       (unsigned long long)s.shm_wakeups);
  family("tpunet_shm_reduce_bytes_total", "counter",
         "Bytes the SHM receive path reduced as they landed (Net::irecv_reduce: "
         "straight out of the ring, or from its bounce buffer); also counted "
         "in tpunet_reduce_bytes_total.");
  emit("tpunet_shm_reduce_bytes_total{rank=\"%lld\"} %llu\n", (long long)rank,
       (unsigned long long)s.shm_reduce_bytes);
  // Request stage-latency histograms: queueing delay separable from wire time.
  auto stage_hist = [&](const char* name, const char* help, const StageHist& h) {
    family(name, "histogram", help);
    uint64_t cum = 0;
    for (int i = 0; i < kStageHistBuckets - 1; ++i) {
      cum += h.buckets[i];
      emit("%s_bucket{rank=\"%lld\",le=\"%llu\"} %llu\n", name, (long long)rank,
           (unsigned long long)kStageHistBounds[i], (unsigned long long)cum);
    }
    cum += h.buckets[kStageHistBuckets - 1];
    emit("%s_bucket{rank=\"%lld\",le=\"+Inf\"} %llu\n", name, (long long)rank,
         (unsigned long long)cum);
    emit("%s_sum{rank=\"%lld\"} %llu\n", name, (long long)rank,
         (unsigned long long)h.sum_us);
    emit("%s_count{rank=\"%lld\"} %llu\n", name, (long long)rank,
         (unsigned long long)h.count);
  };
  stage_hist("tpunet_req_queue_us",
             "Request post to first wire byte (queueing delay, microseconds).",
             s.req_queue_us);
  stage_hist("tpunet_req_wire_us",
             "Request first to last wire byte (wire time, microseconds).",
             s.req_wire_us);
  stage_hist("tpunet_req_total_us",
             "Request post to completion (total latency, microseconds).",
             s.req_total_us);
  // Serving-tier SLO families (docs/DESIGN.md "Serving tier"): per-request
  // TTFT/TPOT fed by the router/decode workers, and instantaneous per-tier
  // queue depths. Every tier series emits even at zero so dashboards (and
  // the serve smoke lane) never look up a missing series.
  stage_hist("tpunet_req_ttft_us",
             "Serving-tier request admission to first generated token "
             "(microseconds).",
             s.req_ttft_us);
  stage_hist("tpunet_req_tpot_us",
             "Serving-tier mean time per output token after the first "
             "(microseconds).",
             s.req_tpot_us);
  family("tpunet_serve_queue_depth", "gauge",
         "Requests queued or held per serving tier (router admission queue, "
         "prefill backlog, decode pending+live slots).");
  static const char* kTierNames[kServeTierCount] = {"router", "prefill",
                                                    "decode"};
  for (int t = 0; t < kServeTierCount; ++t) {
    emit("tpunet_serve_queue_depth{rank=\"%lld\",tier=\"%s\"} %llu\n",
         (long long)rank, kTierNames[t],
         (unsigned long long)s.serve_queue_depth[t]);
  }
  // Elastic-churn families (docs/DESIGN.md "Elastic churn"). Every phase /
  // kind series emits even at zero so the churn smoke lane's "non-empty for
  // EVERY phase" gate never has to special-case a missing series.
  family("tpunet_rewire_duration_us", "histogram",
         "Elastic rewire duration per recovery phase (detect, quiesce, "
         "rendezvous, rewire — microseconds).");
  static const char* kRewirePhases[kRewirePhaseCount] = {
      "detect", "quiesce", "rendezvous", "rewire"};
  for (int p = 0; p < kRewirePhaseCount; ++p) {
    const StageHist& h = s.rewire_us[p];
    uint64_t cum = 0;
    for (int i = 0; i < kStageHistBuckets - 1; ++i) {
      cum += h.buckets[i];
      emit("tpunet_rewire_duration_us_bucket{rank=\"%lld\",phase=\"%s\",le=\"%llu\"} %llu\n",
           (long long)rank, kRewirePhases[p],
           (unsigned long long)kStageHistBounds[i], (unsigned long long)cum);
    }
    cum += h.buckets[kStageHistBuckets - 1];
    emit("tpunet_rewire_duration_us_bucket{rank=\"%lld\",phase=\"%s\",le=\"+Inf\"} %llu\n",
         (long long)rank, kRewirePhases[p], (unsigned long long)cum);
    emit("tpunet_rewire_duration_us_sum{rank=\"%lld\",phase=\"%s\"} %llu\n",
         (long long)rank, kRewirePhases[p], (unsigned long long)h.sum_us);
    emit("tpunet_rewire_duration_us_count{rank=\"%lld\",phase=\"%s\"} %llu\n",
         (long long)rank, kRewirePhases[p], (unsigned long long)h.count);
  }
  family("tpunet_churn_events_total", "counter",
         "Membership-churn events survived, by kind (kill, join, shrink, "
         "grow, readmit).");
  static const char* kChurnKinds[kChurnKindCount] = {"kill", "join", "shrink",
                                                     "grow", "readmit"};
  for (int k = 0; k < kChurnKindCount; ++k) {
    emit("tpunet_churn_events_total{rank=\"%lld\",kind=\"%s\"} %llu\n",
         (long long)rank, kChurnKinds[k],
         (unsigned long long)s.churn_events[k]);
  }
  family("tpunet_world_size", "gauge",
         "Live communicator world size as this rank last reported it (0 "
         "until a churn-aware job reports).");
  emit("tpunet_world_size{rank=\"%lld\"} %llu\n", (long long)rank,
       (unsigned long long)s.world_size);
  // Live weight-update families (docs/DESIGN.md "Live weight updates").
  // Same every-series-even-at-zero discipline as the churn families: the
  // swap smoke lane gates on "every phase non-empty".
  family("tpunet_weight_swap_duration_us", "histogram",
         "Live weight-swap duration per publication phase (announce, "
         "broadcast, verify, flip — microseconds).");
  static const char* kSwapPhases[kSwapPhaseCount] = {"announce", "broadcast",
                                                     "verify", "flip"};
  for (int p = 0; p < kSwapPhaseCount; ++p) {
    const StageHist& h = s.swap_us[p];
    uint64_t cum = 0;
    for (int i = 0; i < kStageHistBuckets - 1; ++i) {
      cum += h.buckets[i];
      emit("tpunet_weight_swap_duration_us_bucket{rank=\"%lld\",phase=\"%s\",le=\"%llu\"} %llu\n",
           (long long)rank, kSwapPhases[p],
           (unsigned long long)kStageHistBounds[i], (unsigned long long)cum);
    }
    cum += h.buckets[kStageHistBuckets - 1];
    emit("tpunet_weight_swap_duration_us_bucket{rank=\"%lld\",phase=\"%s\",le=\"+Inf\"} %llu\n",
         (long long)rank, kSwapPhases[p], (unsigned long long)cum);
    emit("tpunet_weight_swap_duration_us_sum{rank=\"%lld\",phase=\"%s\"} %llu\n",
         (long long)rank, kSwapPhases[p], (unsigned long long)h.sum_us);
    emit("tpunet_weight_swap_duration_us_count{rank=\"%lld\",phase=\"%s\"} %llu\n",
         (long long)rank, kSwapPhases[p], (unsigned long long)h.count);
  }
  family("tpunet_swap_events_total", "counter",
         "Weight-swap events, by kind (publish, commit, abort, retry, "
         "mismatch).");
  static const char* kSwapKinds[kSwapKindCount] = {"publish", "commit",
                                                   "abort", "retry",
                                                   "mismatch"};
  for (int k = 0; k < kSwapKindCount; ++k) {
    emit("tpunet_swap_events_total{rank=\"%lld\",kind=\"%s\"} %llu\n",
         (long long)rank, kSwapKinds[k],
         (unsigned long long)s.swap_events[k]);
  }
  family("tpunet_weight_version", "gauge",
         "Checkpoint version this rank is serving (0 until a versioned "
         "serving tier reports; the swap lane's per-rank flip gate).");
  emit("tpunet_weight_version{rank=\"%lld\"} %llu\n", (long long)rank,
       (unsigned long long)s.weight_version);
  static const char* kBridgeKinds[kBridgeKindCount] = {
      "all_reduce",     "all_reduce_start", "all_reduce_finish", "all_gather",
      "reduce_scatter", "all_to_all",       "broadcast",         "neighbor_exchange"};
  family("tpunet_bridge_calls_total", "counter",
         "Host callbacks the DCN bridge (tpunet/interop.py io_callback "
         "path) ran, by collective kind; the FFI path never counts here.");
  for (int k = 0; k < kBridgeKindCount; ++k) {
    emit("tpunet_bridge_calls_total{rank=\"%lld\",kind=\"%s\"} %llu\n",
         (long long)rank, kBridgeKinds[k],
         (unsigned long long)s.bridge_calls[k]);
  }
  family("tpunet_bridge_bytes_total", "counter",
         "Operand bytes staged through the DCN bridge's host callbacks, by "
         "collective kind.");
  for (int k = 0; k < kBridgeKindCount; ++k) {
    emit("tpunet_bridge_bytes_total{rank=\"%lld\",kind=\"%s\"} %llu\n",
         (long long)rank, kBridgeKinds[k],
         (unsigned long long)s.bridge_bytes[k]);
  }
  family("tpunet_bridge_chunks_total", "counter",
         "Chunks of a boundary exchange (tpunet/interop.py host_all_reduce) "
         "that crossed the bridge, by collective kind: K an exchange.");
  for (int k = 0; k < kBridgeKindCount; ++k) {
    emit("tpunet_bridge_chunks_total{rank=\"%lld\",kind=\"%s\"} %llu\n",
         (long long)rank, kBridgeKinds[k],
         (unsigned long long)s.bridge_chunks[k]);
  }
  family("tpunet_bridge_chunks_in_flight_max", "gauge",
         "Most chunks of one boundary exchange that were between the start of "
         "their copy to the host and the return of their device_put at one "
         "time, by collective kind, since the last reset.");
  for (int k = 0; k < kBridgeKindCount; ++k) {
    emit("tpunet_bridge_chunks_in_flight_max{rank=\"%lld\",kind=\"%s\"} %llu\n",
         (long long)rank, kBridgeKinds[k],
         (unsigned long long)s.bridge_chunks_in_flight_max[k]);
  }
  family("tpunet_bridge_minor_faults_total", "counter",
         "Minor page faults of the process (every thread) across boundary "
         "exchanges (tpunet/interop.py host_all_reduce), by collective kind: "
         "over tpunet_bridge_bytes_total, what the exchange's host blocks "
         "cost in pages that were new to the process.");
  for (int k = 0; k < kBridgeKindCount; ++k) {
    emit("tpunet_bridge_minor_faults_total{rank=\"%lld\",kind=\"%s\"} %llu\n",
         (long long)rank, kBridgeKinds[k],
         (unsigned long long)s.bridge_minor_faults[k]);
  }
  family("tpunet_hold_on_request", "gauge",
         "Requests posted but not yet test()ed done (in flight).");
  emit("tpunet_hold_on_request{rank=\"%lld\"} %llu\n", (long long)rank,
       (unsigned long long)s.inflight);
  family("tpunet_failed_requests", "counter", "Requests that completed with an error.");
  emit("tpunet_failed_requests{rank=\"%lld\"} %llu\n", (long long)rank,
       (unsigned long long)s.failed_requests);
  // Failure-containment counters. faults_injected is labeled by action and
  // emitted only for nonzero slots; the unlabeled totals are always present
  // so dashboards (and the Python parser, which must accept label-less
  // lines) see them even at zero.
  family("tpunet_faults_injected_total", "counter",
         "Deterministic fault injections fired, by action (chaos testing).");
  static const char* kActionNames[kFaultActionSlots] = {"none", "close", "stall",
                                                        "corrupt", "delay"};
  uint64_t faults_total = 0;
  for (int i = 1; i < kFaultActionSlots; ++i) {
    faults_total += s.faults_injected[i];
    if (s.faults_injected[i] == 0) continue;
    emit("tpunet_faults_injected_total{rank=\"%lld\",action=\"%s\"} %llu\n", (long long)rank,
         kActionNames[i], (unsigned long long)s.faults_injected[i]);
  }
  family("tpunet_faults_injected", "counter",
         "Deterministic fault injections fired, all actions (label-less total).");
  emit("tpunet_faults_injected %llu\n", (unsigned long long)faults_total);
  family("tpunet_stream_failovers_total", "counter",
         "Data-stream failures survived via single-stream failover.");
  emit("tpunet_stream_failovers_total{rank=\"%lld\"} %llu\n", (long long)rank,
       (unsigned long long)s.stream_failovers);
  family("tpunet_crc_errors_total", "counter",
         "Per-chunk CRC32C mismatches detected (TPUNET_CRC=1).");
  emit("tpunet_crc_errors_total{rank=\"%lld\"} %llu\n", (long long)rank,
       (unsigned long long)s.crc_errors);
  // Zero-copy data-path counters. All four op slots emit even at zero so
  // syscalls/MiB derivations never divide by a missing series.
  family("tpunet_engine_syscalls_total", "counter",
         "Wire send/recv-family syscalls issued on the engines' data paths, "
         "by syscall op and direction.");
  static const struct {
    const char* op;
    const char* dir;
  } kIoOpLabels[kIoOpCount] = {
      {"send", "tx"}, {"recv", "rx"}, {"sendmsg", "tx"}, {"recvmsg", "rx"}};
  for (int i = 0; i < kIoOpCount; ++i) {
    emit("tpunet_engine_syscalls_total{rank=\"%lld\",op=\"%s\",dir=\"%s\"} %llu\n",
         (long long)rank, kIoOpLabels[i].op, kIoOpLabels[i].dir,
         (unsigned long long)s.engine_syscalls[i]);
  }
  family("tpunet_reduce_bytes_total", "counter",
         "Bytes produced by the collective reduction kernels (output side).");
  emit("tpunet_reduce_bytes_total{rank=\"%lld\"} %llu\n", (long long)rank,
       (unsigned long long)s.reduce_bytes);
  // Compressed-collectives counters. Every codec x dir series emits even at
  // zero so wire-ratio derivations (perf smoke, busbw_sweep) never divide by
  // a missing series.
  family("tpunet_codec_bytes_total", "counter",
         "Encoded bytes produced (tx) and consumed (rx) by the collective "
         "wire codecs, by codec.");
  static const char* kCodecNames[2] = {"bf16", "int8"};
  static const char* kCodecDirs[2] = {"tx", "rx"};
  for (int c = 0; c < 2; ++c) {
    for (int d = 0; d < 2; ++d) {
      emit("tpunet_codec_bytes_total{rank=\"%lld\",codec=\"%s\",dir=\"%s\"} %llu\n",
           (long long)rank, kCodecNames[c], kCodecDirs[d],
           (unsigned long long)s.codec_bytes[c][d]);
    }
  }
  family("tpunet_codec_wire_ratio", "gauge",
         "Encoded wire bytes per f32 payload byte over the compressed "
         "collective paths (1.0 when nothing was compressed).");
  uint64_t codec_encoded = 0, codec_payload = 0;
  for (int c = 0; c < 2; ++c) {
    for (int d = 0; d < 2; ++d) codec_encoded += s.codec_bytes[c][d];
  }
  for (int d = 0; d < 2; ++d) codec_payload += s.codec_payload_bytes[d];
  emit("tpunet_codec_wire_ratio{rank=\"%lld\"} %.6f\n", (long long)rank,
       codec_payload > 0 ? (double)codec_encoded / (double)codec_payload : 1.0);
  // Schedule-dispatch counters (docs/DESIGN.md "Schedules & algorithm
  // selection"). Every algo series emits even at zero so step-budget
  // assertions (perf smoke) can pin "ring executed NO steps" directly.
  // Step slots 3/4 are the hierarchical schedule's two stages: the claim is
  // precisely that hier.inter (the DCN wire rounds) shrinks by ~R x while
  // hier.intra rides shared memory.
  static const char* kAlgoNames[7] = {"ring",       "rhd",       "tree",
                                      "hier.intra", "hier.inter", "a2a.intra",
                                      "a2a.inter"};
  static const char* kSelAlgoNames[6] = {"ring", "rhd",      "tree",
                                         "hier", "hier_a2a", "pairwise"};
  static const char* kCollNames[3] = {"allreduce", "broadcast", "alltoall"};
  family("tpunet_coll_steps_total", "counter",
         "Sequential collective wire rounds executed by this rank, per "
         "schedule (ring AllReduce = 2(W-1); rhd = 2*log2(W'); tree <= "
         "2*ceil(log2 W); hier = 2(R-1) intra-host + 2(H-1) inter-host; "
         "hier AllToAll = R-1 intra + H-1 inter).");
  for (int a = 0; a < 7; ++a) {
    emit("tpunet_coll_steps_total{rank=\"%lld\",algo=\"%s\"} %llu\n",
         (long long)rank, kAlgoNames[a], (unsigned long long)s.coll_steps[a]);
  }
  family("tpunet_coll_algo_selected_total", "counter",
         "Collective dispatch decisions, by collective and RESOLVED "
         "schedule (override > TPUNET_DISPATCH_TABLE > built-ins).");
  for (int k = 0; k < 3; ++k) {
    for (int a = 0; a < 6; ++a) {
      emit("tpunet_coll_algo_selected_total{rank=\"%lld\",coll=\"%s\",algo=\"%s\"} %llu\n",
           (long long)rank, kCollNames[k], kSelAlgoNames[a],
           (unsigned long long)s.coll_algo_selected[k][a]);
    }
  }
  // AllToAll byte accounting per stage (docs/DESIGN.md "Hierarchical
  // AllToAll"). All stage x dir series emit even at zero so the exact-byte
  // gates (tests/test_a2a.py, moe_smoke) never look up a missing series.
  static const char* kA2aStageNames[3] = {"intra", "inter", "flat"};
  family("tpunet_a2a_bytes_total", "counter",
         "AllToAll wire bytes per stage and direction: intra = same-host "
         "regroup hops (SHM-cheap), inter = the one-rank-per-host DCN "
         "transpose, flat = the pairwise mesh / ring relay baseline.");
  for (int st = 0; st < 3; ++st) {
    emit("tpunet_a2a_bytes_total{rank=\"%lld\",stage=\"%s\",dir=\"tx\"} %llu\n",
         (long long)rank, kA2aStageNames[st],
         (unsigned long long)s.a2a_bytes[st][0]);
    emit("tpunet_a2a_bytes_total{rank=\"%lld\",stage=\"%s\",dir=\"rx\"} %llu\n",
         (long long)rank, kA2aStageNames[st],
         (unsigned long long)s.a2a_bytes[st][1]);
  }
  return out;
}

bool Telemetry::FlushTrace() {
  if (!tracing_enabled()) return true;
  Impl* im = impl_.get();
  std::vector<Span> spans;
  {
    MutexLock lk(im->span_mu);
    spans.swap(im->done_spans);
  }
  MutexLock lk(im->span_mu);  // serialize file writes
  if (spans.empty() && im->trace_header_written) return true;
  // The file is VALID JSON after every flush: the array's closing "\n]" is
  // rewritten in place on each append (r+ / seek −2), so json.load and
  // Perfetto both accept it at any point, including mid-run.
  //
  // Guarded state is copied to locals around the write_header lambda: TSA
  // analyzes a lambda as a separate unannotated function, so direct guarded
  // accesses inside it would (falsely) warn even with span_mu held here.
  const std::string path = im->trace_path;
  bool header_written = im->trace_header_written;
  FILE* f = nullptr;
  auto write_header = [&]() -> FILE* {
    FILE* nf = fopen(path.c_str(), "w");
    if (!nf) return nullptr;
    fprintf(nf,
            "[\n{\"name\":\"process_name\",\"ph\":\"M\",\"pid\":%lld,"
            "\"args\":{\"name\":\"tpunet-rank%lld\"}}",
            (long long)im->rank, (long long)im->rank);
    header_written = true;
    return nf;
  };
  if (!header_written) {
    f = write_header();
  } else {
    f = fopen(path.c_str(), "r+");
    if (f) {
      if (fseek(f, -2, SEEK_END) != 0) {
        fclose(f);
        f = nullptr;
      }
    }
    if (!f) f = write_header();  // file deleted/truncated underneath: restart
  }
  if (!f) return false;  // spans dropped; caller surfaces the failure
  im->trace_header_written = header_written;
  for (const Span& s : spans) {
    switch (s.kind) {
      case Span::Kind::kReq:
        // Span naming per the reference: "isend-{comm}" / "irecv-{comm}" with
        // id and nbytes attributes (nthread:529-538).
        fprintf(f,
                ",\n{\"name\":\"%s-%llu\",\"ph\":\"X\",\"pid\":%lld,\"tid\":%llu,"
                "\"ts\":%llu,\"dur\":%llu,\"args\":{\"id\":%llu,\"nbytes\":%llu}}",
                s.is_send ? "isend" : "irecv", (unsigned long long)s.comm,
                (long long)im->rank, (unsigned long long)s.comm,
                (unsigned long long)s.start_us, (unsigned long long)s.dur_us,
                (unsigned long long)s.req, (unsigned long long)s.nbytes);
        break;
      case Span::Kind::kColl:
        // Collective phase span: (comm_id, coll_seq, name) is the cross-rank
        // join key merge_traces() aligns per-rank timelines with. The host
        // tag (utils.h HostId(), hex string so JSON consumers never round
        // a 64-bit id) lets merge_traces() group same-host ranks under ONE
        // Perfetto track group instead of interleaving them.
        fprintf(f,
                ",\n{\"name\":\"%s\",\"ph\":\"X\",\"pid\":%lld,\"tid\":%llu,"
                "\"ts\":%llu,\"dur\":%llu,\"args\":{\"comm_id\":%llu,"
                "\"coll_seq\":%llu,\"nbytes\":%llu,\"host\":\"%016llx\"}}",
                s.name.c_str(), (long long)im->rank,
                (unsigned long long)(s.comm & 0xffff),
                (unsigned long long)s.start_us, (unsigned long long)s.dur_us,
                (unsigned long long)s.comm, (unsigned long long)s.req,
                (unsigned long long)s.nbytes, (unsigned long long)HostId());
        break;
      case Span::Kind::kProg:
        // Program span: a host span of the Python layer. Joined to its
        // parent by (parent, seq); deliberately WITHOUT comm_id/coll_seq,
        // which mark collective phases for merge_traces() and the ring
        // readers.
        fprintf(f,
                ",\n{\"name\":\"%s\",\"ph\":\"X\",\"pid\":%lld,\"tid\":%llu,"
                "\"ts\":%llu,\"dur\":%llu,\"args\":{\"seq\":%llu,"
                "\"nbytes\":%llu%s}}",
                s.name.c_str(), (long long)im->rank, (unsigned long long)s.comm,
                (unsigned long long)s.start_us, (unsigned long long)s.dur_us,
                (unsigned long long)s.req, (unsigned long long)s.nbytes,
                s.extra.c_str());
        break;
      case Span::Kind::kPart:
        // Collective part span: on its phase's track (same tid), joined to
        // the phase by (phase, coll) — deliberately not comm_id/coll_seq.
        fprintf(f,
                ",\n{\"name\":\"%s\",\"ph\":\"X\",\"pid\":%lld,\"tid\":%llu,"
                "\"ts\":%llu,\"dur\":%llu,\"args\":{%s,\"coll\":%llu}}",
                s.name.c_str(), (long long)im->rank,
                (unsigned long long)(s.comm & 0xffff),
                (unsigned long long)s.start_us, (unsigned long long)s.dur_us,
                s.extra.c_str(), (unsigned long long)s.req);
        break;
      case Span::Kind::kInstant:
        fprintf(f,
                ",\n{\"name\":\"%s\",\"ph\":\"i\",\"s\":\"p\",\"pid\":%lld,"
                "\"tid\":%llu,\"ts\":%llu,\"args\":{\"stream\":%llu,"
                "\"srtt_us\":%llu,\"median_srtt_us\":%llu,\"dir\":\"%s\"}}",
                s.name.c_str(), (long long)im->rank, (unsigned long long)s.comm,
                (unsigned long long)s.start_us, (unsigned long long)s.comm,
                (unsigned long long)s.req, (unsigned long long)s.nbytes,
                s.is_send ? "tx" : "rx");
        break;
    }
  }
  fprintf(f, "\n]");
  fclose(f);
  return true;
}

// ---------------------------------------------------------------------------

namespace {

class TelemetryNet : public Net {
 public:
  explicit TelemetryNet(std::unique_ptr<Net> inner) : inner_(std::move(inner)) {}

  int32_t devices() override { return inner_->devices(); }
  Status get_properties(int32_t dev, NetProperties* p) override {
    return inner_->get_properties(dev, p);
  }
  Status listen(int32_t dev, SocketHandle* h, uint64_t* lc) override {
    return inner_->listen(dev, h, lc);
  }
  Status connect(int32_t dev, const SocketHandle& h, uint64_t* sc) override {
    return inner_->connect(dev, h, sc);
  }
  Status accept(uint64_t lc, uint64_t* rc) override { return inner_->accept(lc, rc); }

  Status isend(uint64_t comm, const void* data, size_t n, uint64_t* req) override {
    Status s = inner_->isend(comm, data, n, req);
    if (s.ok()) Telemetry::Get().OnRequestStart(Owner(), true, comm, *req, n);
    return s;
  }
  Status irecv(uint64_t comm, void* data, size_t n, uint64_t* req) override {
    Status s = inner_->irecv(comm, data, n, req);
    if (s.ok()) Telemetry::Get().OnRequestStart(Owner(), false, comm, *req, n);
    return s;
  }
  Status irecv_reduce(uint64_t comm, void* dst, const void* local, size_t n,
                      WireDType dtype, WireRedOp op, uint64_t* req) override {
    Status s = inner_->irecv_reduce(comm, dst, local, n, dtype, op, req);
    if (s.ok()) Telemetry::Get().OnRequestStart(Owner(), false, comm, *req, n);
    return s;
  }
  Status test(uint64_t req, bool* done, size_t* nbytes) override {
    Status s = inner_->test(req, done, nbytes);
    if (!s.ok()) {
      // Invalid = unknown/stale id (double-poll, garbage): the request was
      // never tracked here, so neither the failure counter nor the in-flight
      // gauge may move. Real transport errors DO consume the request id.
      if (s.kind != ErrorKind::kInvalidArgument) {
        Telemetry::Get().OnRequestDone(Owner(), req, /*failed=*/true);
      }
    } else if (*done) {
      Telemetry::Get().OnRequestDone(Owner(), req, /*failed=*/false);
    }
    return s;
  }

  Status wait(uint64_t req, size_t* nbytes) override {
    Status s = inner_->wait(req, nbytes);
    if (!s.ok()) {
      if (s.kind != ErrorKind::kInvalidArgument) {
        Telemetry::Get().OnRequestDone(Owner(), req, /*failed=*/true);
      }
    } else {
      Telemetry::Get().OnRequestDone(Owner(), req, /*failed=*/false);
    }
    return s;
  }

  Status close_send(uint64_t c) override { return inner_->close_send(c); }
  Status close_recv(uint64_t c) override { return inner_->close_recv(c); }
  Status close_listen(uint64_t c) override { return inner_->close_listen(c); }
  void set_traffic_class(int32_t cls) override {
    inner_->set_traffic_class(cls);
  }
  int32_t traffic_class() const override { return inner_->traffic_class(); }

 private:
  uint64_t Owner() const { return reinterpret_cast<uint64_t>(this); }

  std::unique_ptr<Net> inner_;
};

}  // namespace

std::unique_ptr<Net> WrapWithTelemetry(std::unique_ptr<Net> inner) {
  return std::make_unique<TelemetryNet>(std::move(inner));
}

}  // namespace tpunet
