// Collectives self-test: W ranks as THREADS of one process so the sanitizer
// lanes (tsan/asan) can see every cross-rank interaction in the collectives
// layer — ReducePool fork-join, the async ticket worker, comm teardown. The
// Python suite runs these paths multi-process where TSAN is blind.
//
// Coverage: all_reduce (sum, with TPUNET_REDUCE_THREADS>1), reduce_scatter,
// all_gather, broadcast, all_to_all, neighbor_exchange, barrier, and
// overlapping iall_reduce tickets waited out of order, then teardown while
// a ticket is still in flight on one rank (wait-then-destroy on the other),
// and an all_reduce over the SHM engine, equal to the TCP engine's bytes.

#include <atomic>
#include <chrono>
#include <cinttypes>
#include <cmath>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <string>
#include <thread>
#include <vector>

#include "tpunet/c_api.h"

namespace {

constexpr int kWorld = 3;
constexpr uint64_t kCount = 40000;  // spans multiple ring chunks

std::atomic<int> g_failures{0};

#define CHECK_MSG(cond, ...)                                      \
  do {                                                            \
    if (!(cond)) {                                                \
      std::fprintf(stderr, "FAIL %s:%d: ", __FILE__, __LINE__);   \
      std::fprintf(stderr, __VA_ARGS__);                          \
      std::fprintf(stderr, "\n");                                 \
      g_failures.fetch_add(1);                                    \
      return;                                                     \
    }                                                             \
  } while (0)

#define CHECK_OK(expr) CHECK_MSG((expr) == 0, "%s -> %s", #expr, tpunet_c_last_error())

// Compressed-collectives lane (docs/DESIGN.md "Compressed collectives"):
// per codec, an f32 allreduce + reduce_scatter over the quantized ring —
// error-bounded vs the exact sum, cross-rank BIT-IDENTICAL (checked via a
// CRC32C allgather), wire_dtype getter agreeing — plus the negotiation
// failure path: ranks configured with different codecs ALL fail with
// TPUNET_ERR_CODEC. Runs under asan/tsan with the small ring chunks set in
// main(), so the chunked encode/fused-decode-reduce pipeline really cycles.
void codec_rank_main(int rank, int base_port) {
  const char* codecs[2] = {"bf16", "int8"};
  for (int ci = 0; ci < 2; ++ci) {
    std::string coord = "127.0.0.1:" + std::to_string(base_port + 1 + ci);
    uintptr_t comm = 0;
    CHECK_OK(tpunet_comm_create_ex(coord.c_str(), rank, kWorld, codecs[ci], nullptr, nullptr, &comm));
    int32_t wd = -1;
    CHECK_OK(tpunet_comm_wire_dtype(comm, &wd));
    CHECK_MSG(wd == ci + 1, "wire_dtype %d != %d for %s", wd, ci + 1, codecs[ci]);

    std::vector<float> send(kCount), recv(kCount);
    for (uint64_t i = 0; i < kCount; ++i) send[i] = float(rank + 1) + float(i % 7);
    CHECK_OK(tpunet_comm_all_reduce(comm, send.data(), recv.data(), kCount, 0, 0));
    for (uint64_t i = 0; i < kCount; ++i) {
      // Exact sum <= 24; per-hop quantization error is <= amax*2^-8 (bf16)
      // or amax/254 (int8) over <= W hops — 0.5 covers both with margin.
      float expect = float(kWorld * (kWorld + 1) / 2) + float(kWorld * (i % 7));
      CHECK_MSG(std::fabs(recv[i] - expect) < 0.5f, "%s all_reduce[%" PRIu64 "] %f != %f",
                codecs[ci], i, double(recv[i]), double(expect));
    }
    // Cross-rank bit-identity: every rank must hold the SAME quantized
    // bytes (the AG phase forwards encoded frames verbatim).
    uint32_t crc = tpunet_c_crc32c(recv.data(), kCount * 4, 0);
    std::vector<uint32_t> crcs(kWorld, 0);
    CHECK_OK(tpunet_comm_all_gather(comm, &crc, crcs.data(), sizeof(crc)));
    for (int r = 0; r < kWorld; ++r) {
      CHECK_MSG(crcs[r] == crc, "%s result bytes differ between rank %d and %d",
                codecs[ci], rank, r);
    }

    // reduce_scatter rides the same compressed RS pipeline.
    const uint64_t rc = 4096;
    std::vector<float> rs_in(kWorld * rc), rs_out(rc);
    for (uint64_t i = 0; i < rs_in.size(); ++i) rs_in[i] = float(rank) + float(i % 11);
    CHECK_OK(tpunet_comm_reduce_scatter(comm, rs_in.data(), rs_out.data(), rc, 0, 0));
    for (uint64_t i = 0; i < rc; ++i) {
      float expect = float(kWorld * (kWorld - 1) / 2) +
                     float(kWorld) * float((rank * rc + i) % 11);
      CHECK_MSG(std::fabs(rs_out[i] - expect) < 0.5f, "%s reduce_scatter[%" PRIu64 "]",
                codecs[ci], i);
    }
    CHECK_OK(tpunet_comm_destroy(&comm));
  }

  // Negotiation failure: rank 0 asks for bf16, everyone else f32 — every
  // rank must get the typed mismatch, nobody may wedge or succeed.
  {
    std::string coord = "127.0.0.1:" + std::to_string(base_port + 3);
    uintptr_t comm = 0;
    int32_t rcv = tpunet_comm_create_ex(coord.c_str(), rank, kWorld,
                                        rank == 0 ? "bf16" : "f32", nullptr,
                                        nullptr, &comm);
    CHECK_MSG(rcv == TPUNET_ERR_CODEC, "expected TPUNET_ERR_CODEC, got %d (%s)",
              rcv, tpunet_c_last_error());
  }

  // Unknown codec name fails before any socket exists.
  {
    uintptr_t comm = 0;
    int32_t rcv = tpunet_comm_create_ex("127.0.0.1:1", rank, 1, "fp8", nullptr, nullptr, &comm);
    CHECK_MSG(rcv == TPUNET_ERR_INVALID, "expected INVALID for fp8, got %d", rcv);
  }
}

// Schedule lane: the same f32 allreduce pinned to each schedule (ring /
// recursive halving-doubling / binomial tree) must produce BYTE-IDENTICAL
// results — the data is integer-valued, so every summation order is exact
// and any divergence is an indexing/offset bug, not float noise. W=3
// exercises the rhd non-power-of-2 fold and the uneven tree. Also pins the
// algo-mismatch handshake (typed failure on EVERY rank, nobody wedges).
void schedule_rank_main(int rank, int base_port) {
  const char* algos[3] = {"ring", "rhd", "tree"};
  std::vector<float> results[3];
  for (int ai = 0; ai < 3; ++ai) {
    std::string coord = "127.0.0.1:" + std::to_string(base_port + 4 + ai);
    uintptr_t comm = 0;
    CHECK_OK(tpunet_comm_create_ex(coord.c_str(), rank, kWorld, "f32",
                                   algos[ai], nullptr, &comm));
    std::vector<float> send(kCount), recv(kCount);
    for (uint64_t i = 0; i < kCount; ++i)
      send[i] = float(rank + 1) + float(i % 23);
    CHECK_OK(tpunet_comm_all_reduce(comm, send.data(), recv.data(), kCount, 0, 0));
    for (uint64_t i = 0; i < kCount; ++i) {
      float expect = float(kWorld * (kWorld + 1) / 2) + float(kWorld * (i % 23));
      CHECK_MSG(recv[i] == expect, "%s all_reduce[%" PRIu64 "] %f != %f",
                algos[ai], i, double(recv[i]), double(expect));
    }
    // Broadcast rides the schedule dispatch too (tree for small payloads).
    std::vector<uint8_t> bc(2048, rank == 1 ? uint8_t(0x5A) : uint8_t(0));
    CHECK_OK(tpunet_comm_broadcast(comm, bc.data(), bc.size(), 1));
    CHECK_MSG(bc[0] == 0x5A && bc[2047] == 0x5A, "%s broadcast corrupted",
              algos[ai]);
    results[ai] = recv;
    CHECK_OK(tpunet_comm_destroy(&comm));
  }
  CHECK_MSG(memcmp(results[0].data(), results[1].data(), kCount * 4) == 0,
            "ring vs rhd results differ");
  CHECK_MSG(memcmp(results[0].data(), results[2].data(), kCount * 4) == 0,
            "ring vs tree results differ");

  // Algo negotiation failure: rank 0 pins tree, everyone else ring — every
  // rank must fail typed at wiring, before any schedule could half-run.
  {
    std::string coord = "127.0.0.1:" + std::to_string(base_port + 7);
    uintptr_t comm = 0;
    int32_t rcv = tpunet_comm_create_ex(coord.c_str(), rank, kWorld, nullptr,
                                        rank == 0 ? "tree" : "ring", nullptr,
                                        &comm);
    CHECK_MSG(rcv == TPUNET_ERR_INVALID,
              "expected TPUNET_ERR_INVALID for algo mismatch, got %d (%s)", rcv,
              tpunet_c_last_error());
  }

  // Traffic-class negotiation failure: rank 0 wires the latency lane,
  // everyone else bulk — typed on every rank, nobody wedges (half a group
  // on another QoS lane would unbalance the scheduler silently).
  {
    std::string coord = "127.0.0.1:" + std::to_string(base_port + 8);
    uintptr_t comm = 0;
    int32_t rcv = tpunet_comm_create_ex(coord.c_str(), rank, kWorld, nullptr,
                                        nullptr,
                                        rank == 0 ? "latency" : "bulk", &comm);
    CHECK_MSG(rcv == TPUNET_ERR_INVALID,
              "expected TPUNET_ERR_INVALID for class mismatch, got %d (%s)",
              rcv, tpunet_c_last_error());
  }

  // Unknown traffic class fails before any socket exists.
  {
    uintptr_t comm = 0;
    int32_t rcv = tpunet_comm_create_ex("127.0.0.1:1", rank, 1, nullptr,
                                        nullptr, "express", &comm);
    CHECK_MSG(rcv == TPUNET_ERR_INVALID, "expected INVALID for express, got %d",
              rcv);
  }

  // Unknown algo name fails before any socket exists.
  {
    uintptr_t comm = 0;
    int32_t rcv =
        tpunet_comm_create_ex("127.0.0.1:1", rank, 1, nullptr, "star", nullptr, &comm);
    CHECK_MSG(rcv == TPUNET_ERR_INVALID, "expected INVALID for star, got %d", rcv);
  }
}

// The value of a label-less-but-rank counter series in the exposition.
uint64_t Counter(const char* series) {
  std::string text(1 << 20, '\0');
  int32_t len = tpunet_c_metrics_text(&text[0], text.size());
  if (len < 0) return 0;
  size_t at = text.find(series);
  if (at == std::string::npos) return 0;
  at = text.find("} ", at);
  return at == std::string::npos ? 0 : std::strtoull(text.c_str() + at + 2, nullptr, 10);
}

// SHM lane: the same f32 all_reduce over the TCP engine and then over the
// shared-memory engine, whose receive thread reduces each chunk straight
// into the caller's accumulator as it lands (Net::irecv_reduce) while the
// collective thread sends from a disjoint slice. The results must be equal
// to the byte: the ring's reduction order is the same on both engines.
void shm_lane_rank_main(int rank, int port, std::vector<float>* out) {
  std::string coord = "127.0.0.1:" + std::to_string(port);
  uintptr_t comm = 0;
  CHECK_OK(tpunet_comm_create_ex(coord.c_str(), rank, kWorld, "f32", "ring", nullptr, &comm));
  std::vector<float> send(kCount);
  for (uint64_t i = 0; i < kCount; ++i) send[i] = 0.1f * float(rank + 1) + 0.37f * float(i % 13);
  out->assign(kCount, 0.0f);
  CHECK_OK(tpunet_comm_all_reduce(comm, send.data(), out->data(), kCount, 0, 0));
  CHECK_OK(tpunet_comm_destroy(&comm));
}

void rank_main(int rank, const std::string& coordinator) {
  uintptr_t comm = 0;
  CHECK_OK(tpunet_comm_create(coordinator.c_str(), rank, kWorld, &comm));

  // all_reduce(sum) f32, out-of-place + in-place.
  std::vector<float> send(kCount), recv(kCount);
  for (uint64_t i = 0; i < kCount; ++i) send[i] = float(rank + 1) + float(i % 7);
  CHECK_OK(tpunet_comm_all_reduce(comm, send.data(), recv.data(), kCount, 0, 0));
  for (uint64_t i = 0; i < kCount; ++i) {
    float expect = float(kWorld * (kWorld + 1) / 2) + float(kWorld * (i % 7));
    CHECK_MSG(std::fabs(recv[i] - expect) < 1e-3f, "all_reduce[%" PRIu64 "] %f != %f",
              i, double(recv[i]), double(expect));
  }
  CHECK_OK(tpunet_comm_all_reduce(comm, send.data(), send.data(), kCount, 0, 0));
  CHECK_MSG(std::fabs(send[0] - recv[0]) < 1e-3f, "in-place mismatch");

  // reduce_scatter: world*rc elements -> rank's rc slice of the sum.
  const uint64_t rc = 1024;
  std::vector<float> rs_in(kWorld * rc), rs_out(rc);
  for (uint64_t i = 0; i < rs_in.size(); ++i) rs_in[i] = float(rank) + float(i);
  CHECK_OK(tpunet_comm_reduce_scatter(comm, rs_in.data(), rs_out.data(), rc, 0, 0));
  for (uint64_t i = 0; i < rc; ++i) {
    float expect = float(kWorld * (kWorld - 1) / 2) + float(kWorld) * float(rank * rc + i);
    CHECK_MSG(std::fabs(rs_out[i] - expect) < 1e-2f, "reduce_scatter[%" PRIu64 "]", i);
  }

  // all_gather bytes.
  std::vector<uint8_t> ag_in(512, uint8_t(0x40 + rank)), ag_out(kWorld * 512);
  CHECK_OK(tpunet_comm_all_gather(comm, ag_in.data(), ag_out.data(), 512));
  for (int r = 0; r < kWorld; ++r)
    CHECK_MSG(ag_out[r * 512] == uint8_t(0x40 + r), "all_gather rank %d block", r);

  // broadcast from root 1.
  std::vector<uint8_t> bc(777, uint8_t(rank == 1 ? 0xAB : 0));
  CHECK_OK(tpunet_comm_broadcast(comm, bc.data(), bc.size(), 1));
  CHECK_MSG(bc[0] == 0xAB && bc[776] == 0xAB, "broadcast payload");

  // all_to_all: block j for rank j.
  std::vector<uint8_t> a2a_in(kWorld * 256), a2a_out(kWorld * 256);
  for (int j = 0; j < kWorld; ++j)
    std::memset(a2a_in.data() + j * 256, 0x10 * (rank + 1) + j, 256);
  CHECK_OK(tpunet_comm_all_to_all(comm, a2a_in.data(), a2a_out.data(), 256));
  for (int j = 0; j < kWorld; ++j)
    CHECK_MSG(a2a_out[j * 256] == uint8_t(0x10 * (j + 1) + rank),
              "all_to_all block from rank %d", j);
  // In-place: sendbuf == recvbuf (pairwise path must stage outgoing blocks).
  CHECK_OK(tpunet_comm_all_to_all(comm, a2a_in.data(), a2a_in.data(), 256));
  for (int j = 0; j < kWorld; ++j)
    CHECK_MSG(a2a_in[j * 256] == uint8_t(0x10 * (j + 1) + rank),
              "in-place all_to_all block from rank %d", j);

  // Typed all_to_all: f32 blocks (codec f32 here -> exact); the typed
  // entry point and its per-block geometry run under the sanitizers.
  const uint64_t tn = 321;  // odd: blocks must not assume alignment
  std::vector<float> t_in(kWorld * tn), t_out(kWorld * tn);
  for (int j = 0; j < kWorld; ++j)
    for (uint64_t i = 0; i < tn; ++i)
      t_in[j * tn + i] = float(rank * 100 + j) + float(i) / 8.0f;
  CHECK_OK(tpunet_comm_all_to_all_typed(comm, t_in.data(), t_out.data(), tn, 0));
  for (int j = 0; j < kWorld; ++j)
    for (uint64_t i = 0; i < tn; ++i)
      CHECK_MSG(t_out[j * tn + i] == float(j * 100 + rank) + float(i) / 8.0f,
                "typed all_to_all block from rank %d elem %" PRIu64, j, i);

  // Async all_to_all ticket outstanding TOGETHER with a ring AllReduce
  // ticket — the mesh-queue overlap contract (tickets on disjoint comms).
  {
    std::vector<float> red(8192, float(rank + 1));
    uint64_t t_red = 0, t_a2a = 0;
    std::vector<uint8_t> ai(kWorld * 128), ao(kWorld * 128);
    for (int j = 0; j < kWorld; ++j)
      std::memset(ai.data() + j * 128, 0x20 * (rank + 1) + j, 128);
    CHECK_OK(tpunet_comm_iall_reduce(comm, red.data(), red.data(), 8192, 0, 0,
                                     &t_red));
    CHECK_OK(tpunet_comm_iall_to_all(comm, ai.data(), ao.data(), 128, &t_a2a));
    CHECK_OK(tpunet_comm_ticket_wait(comm, t_a2a));
    CHECK_OK(tpunet_comm_ticket_wait(comm, t_red));
    CHECK_MSG(std::fabs(red[0] - float(kWorld * (kWorld + 1) / 2)) < 1e-3f,
              "overlapped iall_reduce result");
    for (int j = 0; j < kWorld; ++j)
      CHECK_MSG(ao[j * 128] == uint8_t(0x20 * (j + 1) + rank),
                "iall_to_all block from rank %d", j);
  }

  // neighbor exchange.
  std::vector<uint8_t> ne_in(300, uint8_t(rank)), ne_out(400);
  uint64_t got = 0;
  CHECK_OK(tpunet_comm_neighbor_exchange(comm, ne_in.data(), ne_in.size(),
                                         ne_out.data(), ne_out.size(), &got));
  CHECK_MSG(got == 300 && ne_out[0] == uint8_t((rank + kWorld - 1) % kWorld),
            "neighbor_exchange");

  // Overlapping async tickets waited in reverse order.
  const uint64_t ac = 8192;
  std::vector<std::vector<float>> abufs;
  std::vector<uint64_t> tickets;
  for (int s = 0; s < 3; ++s) {
    abufs.emplace_back(ac, float(rank + 1) * float(s + 1));
    uint64_t t = 0;
    CHECK_OK(tpunet_comm_iall_reduce(comm, abufs[s].data(), abufs[s].data(),
                                     ac, 0, 0, &t));
    tickets.push_back(t);
  }
  for (int s = 2; s >= 0; --s) {
    CHECK_OK(tpunet_comm_ticket_wait(comm, tickets[s]));
    float expect = float(kWorld * (kWorld + 1) / 2) * float(s + 1);
    CHECK_MSG(std::fabs(abufs[s][0] - expect) < 1e-3f, "iall_reduce s=%d", s);
  }

  // ticket_test polling path.
  uint64_t t = 0;
  std::vector<float> last(ac, 1.0f);
  CHECK_OK(tpunet_comm_iall_reduce(comm, last.data(), last.data(), ac, 0, 0, &t));
  uint8_t done = 0;
  CHECK_OK(tpunet_comm_ticket_test(comm, t, &done));  // may or may not be done
  CHECK_OK(tpunet_comm_ticket_wait(comm, t));
  CHECK_OK(tpunet_comm_barrier(comm));

  // Teardown with a ticket still outstanding: destroy must terminate on
  // every interleaving — job drained by the worker, failed while queued, or
  // cut short by a peer's teardown (comm poisoning turns that into a typed
  // error, not a hang; the main() watchdog converts any regression here
  // into a test failure). Buffers stay alive across destroy per the
  // contract. No wait: the ticket is abandoned deliberately.
  uint64_t t2 = 0;
  std::vector<float> tail(ac, 2.0f);
  CHECK_OK(tpunet_comm_iall_reduce(comm, tail.data(), tail.data(), ac, 0, 0, &t2));
  CHECK_OK(tpunet_comm_destroy(&comm));
}

}  // namespace

int main() {
  // Exercise the fork-join reduce pool under the sanitizer.
  setenv("TPUNET_REDUCE_THREADS", "2", 1);
  // Small ring chunks so the pipelined transfer||reduce path really cycles.
  setenv("TPUNET_RING_CHUNKSIZE", "16384", 1);

  const char* port_env = getenv("TPUNET_TEST_PORT");
  int base_port = port_env ? atoi(port_env) : 29517;
  std::string coordinator = "127.0.0.1:" + std::to_string(base_port);

  // A failed check on one rank-thread leaves its peers blocked in the next
  // collective (no data-plane timeout); without a watchdog that is a CI
  // hang, not an exit-1.
  std::atomic<bool> finished{false};
  std::thread watchdog([&finished] {
    for (int i = 0; i < 2400 && !finished.load(); ++i)
      std::this_thread::sleep_for(std::chrono::milliseconds(100));
    if (!finished.load()) {
      std::fprintf(stderr, "FAILED: watchdog timeout (rank deadlock)\n");
      std::_Exit(2);
    }
  });

  std::vector<std::thread> ranks;
  ranks.reserve(kWorld);
  for (int r = 0; r < kWorld; ++r)
    ranks.emplace_back(rank_main, r, coordinator);
  for (auto& th : ranks) th.join();

  // Compressed-collectives lane (fresh comms on base_port+1..+3).
  ranks.clear();
  for (int r = 0; r < kWorld; ++r)
    ranks.emplace_back(codec_rank_main, r, base_port);
  for (auto& th : ranks) th.join();

  // Schedule lane: ring vs rhd vs tree bit-equality + algo handshake
  // (fresh comms on base_port+4..+8).
  ranks.clear();
  for (int r = 0; r < kWorld; ++r)
    ranks.emplace_back(schedule_rank_main, r, base_port);
  for (auto& th : ranks) th.join();

  // SHM lane (fresh comms on base_port+9 over TCP, +10 over SHM on a small
  // ring whose chunks wrap).
  std::vector<float> by_engine[2][kWorld];
  for (int shm = 0; shm < 2; ++shm) {
    if (shm == 1) {
      setenv("TPUNET_SHM", "1", 1);
      setenv("TPUNET_SHM_RING_BYTES", "65536", 1);
    }
    tpunet_c_metrics_reset();
    ranks.clear();
    for (int r = 0; r < kWorld; ++r)
      ranks.emplace_back(shm_lane_rank_main, r, base_port + 9 + shm, &by_engine[shm][r]);
    for (auto& th : ranks) th.join();
    // Every byte the ring reduced landed reduced on SHM, and none on TCP.
    uint64_t landed = Counter("tpunet_shm_reduce_bytes_total{");
    uint64_t reduced = Counter("tpunet_reduce_bytes_total{");
    if (reduced == 0 || landed != (shm == 1 ? reduced : 0)) {
      std::fprintf(stderr, "FAIL: shm=%d reduced %" PRIu64 " bytes, %" PRIu64 " as they landed\n",
                   shm, reduced, landed);
      g_failures.fetch_add(1);
    }
  }
  unsetenv("TPUNET_SHM");
  unsetenv("TPUNET_SHM_RING_BYTES");
  for (int r = 0; r < kWorld; ++r) {
    if (by_engine[0][r] != by_engine[1][r] ||
        memcmp(by_engine[0][r].data(), by_engine[1][r].data(), kCount * 4) != 0) {
      std::fprintf(stderr, "FAIL: rank %d SHM all_reduce differs from TCP\n", r);
      g_failures.fetch_add(1);
    }
  }

  finished.store(true);
  watchdog.join();

  if (g_failures.load() != 0) {
    std::fprintf(stderr, "FAILED: %d check(s)\n", g_failures.load());
    return 1;
  }
  std::printf("OK: all collectives tests passed (%d ranks in-process)\n", kWorld);
  return 0;
}
