// Ring schedule: the chunk-pipelined reduce-scatter + all-gather AllReduce
// (2(W-1) wire rounds, busbw-optimal 2(W-1)/W bytes per element), standalone
// ReduceScatter/AllGather phases, and the pipelined Broadcast relay — plus
// the exchange primitives every schedule shares (Exchange, the chunked
// ExchangeReduce pipeline, and the fused codec variants).
//
// The ring is latency-pessimal (linear round count) but owns the large-
// message end: its chunk pipeline overlaps reduction with transfer, the
// codec fuses decode+reduce off the recv slot, and slices forward encoded
// bytes verbatim in the AG phase (cross-rank bit-identical results). The
// per-size selector (dispatch.h) hands small payloads to the rhd/tree
// schedules instead.
#include <string.h>

#include <algorithm>
#include <string>
#include <vector>

#include "coll_comm.h"

namespace tpunet {
namespace internal {

Status ScheduledCommunicator::DoAllReduceRing(const void* sendbuf, void* recvbuf,
                                              size_t count, DType dtype, RedOp op,
                                              RingChannel& ch, uint64_t seq) {
  size_t esize = DTypeSize(dtype);
  const bool tracing = Telemetry::Get().tracing_enabled();
  PhaseSpan whole(tracing, trace_comm_id_, seq, "allreduce", -1, count * esize);
  const uint8_t* src = static_cast<const uint8_t*>(sendbuf);
  uint8_t* data = static_cast<uint8_t*>(recvbuf);
  // Out-of-place with DISJOINT buffers needs no staging copy at all:
  // round 0 sends from the caller's sendbuf, later rounds send the slice
  // reduced the previous round (already in recvbuf), and every reduce
  // reads its local operand from sendbuf while writing into recvbuf —
  // every recvbuf slice is written (by RS or AG) before anything reads
  // it, so the caller's input never needs to be there. Measured 2x
  // on the 128 MiB out-of-place path (PERF_NOTES round 4): the memcpy
  // plus first-touch faulting of a cold 128 MiB destination was as
  // expensive as the whole ring on a 1-core host. Partially-overlapping
  // buffers (C-ABI callers only; the Python binding never does this)
  // keep the safe copy path.
  bool oop = sendbuf != recvbuf;
  if (oop && src < data + count * esize && data < src + count * esize) {
    // Overlapping: stage (memmove — the ranges provably overlap).
    memmove(recvbuf, sendbuf, count * esize);
    oop = false;
  }
  const int W = world_;
  auto off = [&](int i) { return (count * static_cast<size_t>(i)) / W; };

  // vr relabels the ring so this rank finishes the RS phase owning slice
  // `rank`, which the AG phase then circulates.
  const int vr = (rank_ + W - 1) % W;
  const bool codec_on = UseCodec(dtype);
  size_t ag_slot = 0;
  if (codec_on) {
    // Park the AG phase's two wire slots at the BOTTOM of the channel
    // scratch, before any RS chunk slot: the RS final round's fused
    // handoff writes the owned slice's encoded bytes into AG slot 0, and
    // they must survive the RS rounds' own scratch use.
    ag_slot = CodecWireBytes(codec_, (count + W - 1) / W);
    ch.scratch.reserve(2 * ag_slot +
                       4 * CodecWireBytes(codec_, CodecChunkElems()));
  }
  for (int s = 0; s < W - 1; ++s) {
    int sidx = (vr - s + W) % W;
    int ridx = (vr - s - 1 + W) % W;
    size_t sbytes = (off(sidx + 1) - off(sidx)) * esize;
    size_t rbytes = (off(ridx + 1) - off(ridx)) * esize;
    // Round s sends the slice reduced in round s-1; only round 0's send
    // operand still lives in sendbuf on the no-copy path.
    const uint8_t* sptr =
        ((oop && s == 0) ? src : data) + off(sidx) * esize;
    PhaseSpan step(tracing, trace_comm_id_, seq, "rs", s, sbytes);
    CountCollSteps(CollAlgo::kRing);
    Status st;
    if (codec_on) {
      // Final round reduces into this rank's owned slice (ridx == rank_):
      // fuse the AG-entry quantize+encode into it.
      uint8_t* fused = (s == W - 2) ? ch.scratch.data() : nullptr;
      st = ExchangeReduceCodec(sptr, sbytes, data + off(ridx) * esize,
                               rbytes, op, ch,
                               oop ? src + off(ridx) * esize : nullptr,
                               fused, 2 * ag_slot);
    } else {
      st = ExchangeReduce(sptr, sbytes, data + off(ridx) * esize,
                          rbytes, dtype, op, ch,
                          oop ? src + off(ridx) * esize : nullptr);
    }
    if (!st.ok()) return st;
  }
  if (codec_on) {
    return AgPhaseCodec(reinterpret_cast<float*>(data), count, ch, seq, tracing);
  }
  for (int s = 0; s < W - 1; ++s) {
    int sidx = (rank_ - s + W) % W;
    int ridx = (rank_ - s - 1 + W) % W;
    size_t sbytes = (off(sidx + 1) - off(sidx)) * esize;
    size_t rbytes = (off(ridx + 1) - off(ridx)) * esize;
    PhaseSpan step(tracing, trace_comm_id_, seq, "ag", s, sbytes);
    CountCollSteps(CollAlgo::kRing);
    Status st = Exchange(data + off(sidx) * esize, sbytes, data + off(ridx) * esize,
                         rbytes, nullptr, ch);
    if (!st.ok()) return st;
  }
  return Status::Ok();
}

Status ScheduledCommunicator::ReduceScatter(const void* sendbuf, void* recvbuf,
                                            size_t recv_count, DType dtype,
                                            RedOp op) {
  FenceAsync();
  size_t esize = DTypeSize(dtype);
  if (esize == 0) return Status::Invalid("bad dtype");
  if (recv_count == 0) return Status::Ok();
  const int W = world_;
  if (W == 1) {
    if (sendbuf != recvbuf) memcpy(recvbuf, sendbuf, recv_count * esize);
    return Status::Ok();
  }
  size_t block = recv_count * esize;
  const uint8_t* src = static_cast<const uint8_t*>(sendbuf);
  uint8_t* out = static_cast<uint8_t*>(recvbuf);
  const bool tracing = Telemetry::Get().tracing_enabled();
  const uint64_t seq = ++coll_seq_;
  PhaseSpan whole(tracing, trace_comm_id_, seq, "reduce_scatter", -1,
                  static_cast<uint64_t>(W) * block);
  if (out < src + static_cast<size_t>(W) * block && src < out + block) {
    // Overlapping C-ABI buffers: keep the safe full-copy path.
    work_.reserve(static_cast<size_t>(W) * block);
    memcpy(work_.data(), sendbuf, static_cast<size_t>(W) * block);
    const int vr0 = (rank_ + W - 1) % W;
    for (int s = 0; s < W - 1; ++s) {
      int sidx = (vr0 - s + W) % W;
      int ridx = (vr0 - s - 1 + W) % W;
      PhaseSpan step(tracing, trace_comm_id_, seq, "rs", s, block);
      CountCollSteps(CollAlgo::kRing);
      Status st = ExchangeReduce(work_.data() + sidx * block, block,
                                 work_.data() + ridx * block, block, dtype, op, channels_[0]);
      if (!st.ok()) return st;
    }
    memcpy(recvbuf, work_.data() + rank_ * block, block);
    return Status::Ok();
  }
  // No staging copy of the W-block input: each round's reduce reads its
  // local operand from the caller's sendbuf; partials land in a 2-block
  // ping-pong scratch (a round's output is the NEXT round's send
  // operand), and the final round — whose target is this rank's owned
  // block — writes straight into recvbuf. Scratch is 2 blocks instead of
  // the previous W, and the O(W·B) memcpy is gone. W=2's single round
  // goes sendbuf->recvbuf directly and needs no scratch at all (resizing
  // it would zero-fill + fault pages for nothing — the cost class this
  // path exists to avoid).
  uint8_t* pb[2] = {nullptr, nullptr};
  if (W > 2) {
    work_.reserve(2 * block);
    pb[0] = work_.data();
    pb[1] = work_.data() + block;
  }  // W==2: single round goes sendbuf->recvbuf, pb never read
  const int vr = (rank_ + W - 1) % W;
  for (int s = 0; s < W - 1; ++s) {
    int sidx = (vr - s + W) % W;
    int ridx = (vr - s - 1 + W) % W;
    const uint8_t* sptr = (s == 0) ? src + sidx * block : pb[(s - 1) & 1];
    uint8_t* optr = (s == W - 2) ? out : pb[s & 1];
    PhaseSpan step(tracing, trace_comm_id_, seq, "rs", s, block);
    CountCollSteps(CollAlgo::kRing);
    Status st = ExchangeReduce(sptr, block, optr, block, dtype, op,
                               channels_[0], src + ridx * block);
    if (!st.ok()) return st;
  }
  return Status::Ok();
}

Status ScheduledCommunicator::AllGather(const void* sendbuf, void* recvbuf,
                                        size_t bytes_per_rank) {
  FenceAsync();
  const int W = world_;
  uint8_t* out = static_cast<uint8_t*>(recvbuf);
  if (out + rank_ * bytes_per_rank != sendbuf) {
    memcpy(out + rank_ * bytes_per_rank, sendbuf, bytes_per_rank);
  }
  if (W == 1 || bytes_per_rank == 0) return Status::Ok();
  const bool tracing = Telemetry::Get().tracing_enabled();
  const uint64_t seq = ++coll_seq_;
  PhaseSpan whole(tracing, trace_comm_id_, seq, "all_gather", -1,
                  static_cast<uint64_t>(W) * bytes_per_rank);
  for (int s = 0; s < W - 1; ++s) {
    int sidx = (rank_ - s + W) % W;
    int ridx = (rank_ - s - 1 + W) % W;
    PhaseSpan step(tracing, trace_comm_id_, seq, "ag", s, bytes_per_rank);
    CountCollSteps(CollAlgo::kRing);
    Status st = Exchange(out + sidx * bytes_per_rank, bytes_per_rank,
                         out + ridx * bytes_per_rank, bytes_per_rank, nullptr, channels_[0]);
    if (!st.ok()) return st;
  }
  return Status::Ok();
}

Status ScheduledCommunicator::DoBroadcastRing(void* buf, size_t nbytes, int root,
                                              uint64_t seq) {
  const int W = world_;
  PhaseSpan whole(Telemetry::Get().tracing_enabled(), trace_comm_id_, seq,
                  "broadcast", -1, nbytes);
  uint8_t* data = static_cast<uint8_t*>(buf);
  int dist = (rank_ - root + W) % W;          // hops from root along the ring
  bool is_tail = dist == W - 1;               // last rank forwards nothing
  size_t nchunks = (nbytes + kBcastChunk - 1) / kBcastChunk;
  // Steps counter: one sequential recv round (non-root) + one forward round
  // (non-tail) — the chunked pipeline inside a round is overlap, not extra
  // latency hops.
  CountCollSteps(CollAlgo::kRing, (dist != 0 ? 1 : 0) + (is_tail ? 0 : 1));

  // Pipelined forward: receive chunk c, then send it on while chunk c+1 is
  // in flight — the ring streams instead of store-and-forwarding the
  // whole buffer W-1 times.
  std::vector<uint64_t> pending_sends;
  for (size_t c = 0; c < nchunks; ++c) {
    size_t coff = c * kBcastChunk;
    size_t clen = std::min(kBcastChunk, nbytes - coff);
    if (dist != 0) {
      uint64_t rreq = 0;
      Status st = PostRecv(channels_[0].recv_comm, data + coff, clen, &rreq);
      if (!st.ok()) return DrainSends(pending_sends, st);
      size_t got = 0;
      st = WaitRecv(rreq, &got);
      if (!st.ok()) return DrainSends(pending_sends, st);
      if (got != clen) {
        return DrainSends(pending_sends, Status::Inner("broadcast chunk size mismatch"));
      }
    }
    if (!is_tail) {
      uint64_t sreq = 0;
      Status st = PostSend(channels_[0].send_comm, data + coff, clen, &sreq);
      if (!st.ok()) return DrainSends(pending_sends, st);
      pending_sends.push_back(sreq);
    }
  }
  return DrainSends(pending_sends, Status::Ok());
}

// ---------------------------------------------------------------------------
// Exchange primitives (shared by every schedule and the wiring quiesces).

// One pipelined reduce ring step: send `sendbuf` to next while receiving
// the same-size slice from prev in chunks, folding each received chunk
// into `accum` (element count = slice bytes / esize) as soon as it lands —
// chunk i's Reduce overlaps chunk i+1's transfer. Double-buffered scratch;
// all in-flight requests are quiesced before returning, even on error.
// `local` is the left operand of the reduce (accum = local op incoming);
// nullptr = accum itself (the classic in-place accumulate). A distinct
// local lets out-of-place collectives read the caller's sendbuf directly
// and write partials straight into recvbuf — no staging copy anywhere.
//
// Where the recv comm reduces as it lands (Net::irecv_reduce: the SHM
// engine's own comms), every recv is posted that way straight into
// `accum`: no scratch landing and no Reduce here, and the reduce's time
// lies inside the recv wait (coll.wait_wire). Sends and chunk sizes are the
// same either way, so the peer cannot tell which path this rank took.
Status ScheduledCommunicator::ExchangeReduce(const uint8_t* sendbuf, size_t send_nbytes,
                                             uint8_t* accum, size_t recv_nbytes,
                                             DType dtype, RedOp op, RingChannel& ch,
                                             const uint8_t* local) {
  if (local == nullptr) local = accum;
  if (UseCodec(dtype)) {
    return ExchangeReduceCodec(sendbuf, send_nbytes, accum, recv_nbytes, op,
                               ch, local);
  }
  size_t esize = DTypeSize(dtype);
  size_t chunk = RingChunkBytes() / esize * esize;
  if (chunk == 0 || (send_nbytes <= chunk && recv_nbytes <= chunk)) {
    if (!ch.recv_copies_only) {
      uint64_t rreq = 0;
      if (PostRecvReduce(ch.recv_comm, accum, local, recv_nbytes, dtype, op, &rreq).ok()) {
        return ExchangePosted(rreq, sendbuf, send_nbytes, recv_nbytes, nullptr, ch);
      }
      ch.recv_copies_only = true;
    }
    ch.scratch.reserve(recv_nbytes);
    Status st = Exchange(sendbuf, send_nbytes, ch.scratch.data(), recv_nbytes, nullptr, ch);
    if (!st.ok()) return st;
    Reduce(accum, local, ch.scratch.data(), recv_nbytes / esize, dtype, op);
    return Status::Ok();
  }
  // Send and recv slice sizes can differ (ring slices are count*i/W
  // splits); each side chunks ITS byte count with the shared chunk size,
  // which matches what the peer computes for the same bytes. A chunk-size
  // mismatch between ranks surfaces as a size-mismatch error below.
  size_t ns = (send_nbytes + chunk - 1) / chunk;
  size_t nr = (recv_nbytes + chunk - 1) / chunk;
  size_t n = std::max(ns, nr);
  auto slen = [&](size_t i) { return std::min(chunk, send_nbytes - i * chunk); };
  auto rlen = [&](size_t i) { return std::min(chunk, recv_nbytes - i * chunk); };

  uint64_t rreq[2] = {0, 0}, sreq[2] = {0, 0};
  bool rlive[2] = {false, false}, slive[2] = {false, false};
  bool landed = false;  // the first recv post decides: the comm reduces as it lands
  auto post = [&](size_t i) -> Status {
    int slot = i & 1;
    if (i < nr) {
      Status st;
      if (i == 0 && !ch.recv_copies_only) {
        landed = PostRecvReduce(ch.recv_comm, accum, local, rlen(0), dtype, op, &rreq[slot]).ok();
        ch.recv_copies_only = !landed;
      } else if (landed) {
        st = PostRecvReduce(ch.recv_comm, accum + i * chunk, local + i * chunk, rlen(i),
                            dtype, op, &rreq[slot]);
      }
      if (!landed) {
        ch.scratch.reserve(2 * chunk);
        st = PostRecv(ch.recv_comm, ch.scratch.data() + slot * chunk, rlen(i), &rreq[slot]);
      }
      if (!st.ok()) return st;
      rlive[slot] = true;
    }
    if (i < ns) {
      Status st = PostSend(ch.send_comm, sendbuf + i * chunk, slen(i), &sreq[slot]);
      if (!st.ok()) return st;
      slive[slot] = true;
    }
    return Status::Ok();
  };
  auto quiesce = [&](Status primary) {
    for (int b = 0; b < 2; ++b) {
      if (rlive[b]) WaitRecv(rreq[b], nullptr);
      if (slive[b]) WaitSend(sreq[b]);
    }
    return primary;
  };

  Status st = post(0);
  if (!st.ok()) return quiesce(st);
  for (size_t i = 0; i < n; ++i) {
    int slot = i & 1;
    bool has_r = i < nr;
    if (has_r) {
      size_t got = 0;
      st = WaitRecv(rreq[slot], &got);
      rlive[slot] = false;
      if (!st.ok()) return quiesce(st);
      if (got != rlen(i)) {
        return quiesce(Status::Inner(
            "ring step size mismatch: expected " + std::to_string(rlen(i)) +
            "B chunk, got " + std::to_string(got) +
            "B (ranks disagree on collective arguments or TPUNET_RING_CHUNKSIZE?)"));
      }
    }
    if (i + 1 < n) {
      st = post(i + 1);  // keep the wire busy while we reduce chunk i
      if (!st.ok()) return quiesce(st);
    }
    if (has_r && !landed) {
      Reduce(accum + i * chunk, local + i * chunk,
             ch.scratch.data() + slot * chunk, rlen(i) / esize, dtype, op);
    }
    if (i < ns) {
      st = WaitSend(sreq[slot]);
      slive[slot] = false;
      if (!st.ok()) return quiesce(st);
    }
  }
  return Status::Ok();
}

// Payload elements per pipeline chunk, sized so the WIRE chunk — not the
// payload chunk — lands on the tuned TPUNET_RING_CHUNKSIZE granularity:
// the ring's per-chunk costs (ctrl frames, request churn, stream
// scheduling) are paid per chunk regardless of its size, so a compressed
// chunk must carry as many wire bytes as an uncompressed one or
// compression halves the bytes but none of the per-chunk overhead
// (measured: payload-sized bf16 chunks left the whole RS phase at f32
// speed). int8 chunks stay multiples of the scale block so the per-chunk
// encoding is byte-identical to a whole-slice encode (the fused RS->AG
// handoff and the AG receiver both rely on that).
size_t ScheduledCommunicator::CodecChunkElems() const {
  size_t ce;
  switch (codec_) {
    case WireCodec::kBF16:
      ce = RingChunkBytes() / 2;  // 2 wire bytes per element
      break;
    case WireCodec::kI8:
      ce = RingChunkBytes() & ~(kI8CodecBlock - 1);  // ~1 wire byte/element
      if (ce < kI8CodecBlock) ce = kI8CodecBlock;
      break;
    default:
      ce = RingChunkBytes() / 4;
      break;
  }
  return std::max<size_t>(ce, 1);
}

// Codec variant of ExchangeReduce for f32 payloads (docs/DESIGN.md
// "Compressed collectives"): each chunk is ENCODED into a scratch slot
// right before its isend and runs a FUSED decode+reduce straight off the
// recv slot — the accumulator (and the local operand) stay f32, so
// quantization error enters once per wire hop and never compounds in the
// running sum. Chunk boundaries are computed over ELEMENT counts exactly
// like the uncompressed path, so both peers derive identical per-chunk
// wire sizes from their own payload byte counts; a rank disagreement
// surfaces as the same size-mismatch error. Double-buffered recv AND send
// slots (the encode is a staging copy the zero-copy f32 path avoids —
// that copy is the price of shipping half/quarter the bytes).
// `fused_enc` (optional): run the RS->AG handoff kernel on every received
// chunk — the accumulator comes out QUANTIZED (bit-identical to what peers
// will decode) and its encoded form lands at fused_enc, laid out exactly
// like a whole-slice encode, ready to be the AG phase's first send.
// `scratch_off`: byte offset into ch.scratch below which the caller has
// staged bytes this call must not clobber.
Status ScheduledCommunicator::ExchangeReduceCodec(
    const uint8_t* sendbuf, size_t send_nbytes, uint8_t* accum, size_t recv_nbytes,
    RedOp op, RingChannel& ch, const uint8_t* local, uint8_t* fused_enc,
    size_t scratch_off) {
  if (local == nullptr) local = accum;  // classic in-place accumulate
  const float* send_f = reinterpret_cast<const float*>(sendbuf);
  float* acc_f = reinterpret_cast<float*>(accum);
  const float* loc_f = reinterpret_cast<const float*>(local);
  const WireRedOp wop = ToWireRedOp(op);
  const size_t send_n = send_nbytes / 4;
  const size_t recv_n = recv_nbytes / 4;
  const size_t chunk_elems = CodecChunkElems();

  if (send_n <= chunk_elems && recv_n <= chunk_elems) {
    size_t rw = CodecWireBytes(codec_, recv_n);
    size_t sw = CodecWireBytes(codec_, send_n);
    ch.scratch.reserve(scratch_off + rw + sw);
    uint8_t* rbuf = ch.scratch.data() + scratch_off;
    uint8_t* sbuf = rbuf + rw;
    CodecEncode(codec_, send_f, sbuf, send_n);
    Status st = Exchange(sbuf, sw, rbuf, rw, nullptr, ch);
    if (!st.ok()) return st;
    if (fused_enc != nullptr) {
      DecodeReduceQuantize(codec_, acc_f, loc_f, rbuf, fused_enc, recv_n, wop);
    } else {
      DecodeReduce(codec_, acc_f, loc_f, rbuf, recv_n, wop);
    }
    return Status::Ok();
  }

  const size_t ns = (send_n + chunk_elems - 1) / chunk_elems;
  const size_t nr = (recv_n + chunk_elems - 1) / chunk_elems;
  const size_t n = std::max(ns, nr);
  const size_t slot_bytes = CodecWireBytes(codec_, chunk_elems);
  // 2 recv + 2 send wire slots, after whatever the caller staged below
  // scratch_off (DoAllReduceRing parks the AG slots there — reserve only
  // grows, so their bytes survive this call).
  ch.scratch.reserve(scratch_off + 4 * slot_bytes);
  uint8_t* base = ch.scratch.data() + scratch_off;
  auto rbuf = [&](size_t i) { return base + (i & 1) * slot_bytes; };
  auto sbuf = [&](size_t i) { return base + (2 + (i & 1)) * slot_bytes; };
  auto selems = [&](size_t i) { return std::min(chunk_elems, send_n - i * chunk_elems); };
  auto relems = [&](size_t i) { return std::min(chunk_elems, recv_n - i * chunk_elems); };

  uint64_t rreq[2] = {0, 0}, sreq[2] = {0, 0};
  bool rlive[2] = {false, false}, slive[2] = {false, false};
  auto post = [&](size_t i) -> Status {
    int slot = i & 1;
    if (i < nr) {
      Status st = PostRecv(ch.recv_comm, rbuf(i),
                              CodecWireBytes(codec_, relems(i)), &rreq[slot]);
      if (!st.ok()) return st;
      rlive[slot] = true;
    }
    if (i < ns) {
      // Encode right before the isend: slot (i&1)'s previous send (i-2)
      // was waited at the tail of iteration i-2, so the staging bytes are
      // free to overwrite, and the encode of chunk i overlaps the wire
      // moving chunk i-1.
      CodecEncode(codec_, send_f + i * chunk_elems, sbuf(i), selems(i));
      Status st = PostSend(ch.send_comm, sbuf(i),
                              CodecWireBytes(codec_, selems(i)), &sreq[slot]);
      if (!st.ok()) return st;
      slive[slot] = true;
    }
    return Status::Ok();
  };
  auto quiesce = [&](Status primary) {
    for (int b = 0; b < 2; ++b) {
      if (rlive[b]) WaitRecv(rreq[b], nullptr);
      if (slive[b]) WaitSend(sreq[b]);
    }
    return primary;
  };

  Status st = post(0);
  if (!st.ok()) return quiesce(st);
  for (size_t i = 0; i < n; ++i) {
    int slot = i & 1;
    bool has_r = i < nr;
    if (has_r) {
      size_t got = 0;
      st = WaitRecv(rreq[slot], &got);
      rlive[slot] = false;
      if (!st.ok()) return quiesce(st);
      if (got != CodecWireBytes(codec_, relems(i))) {
        return quiesce(Status::Inner(
            "ring step size mismatch: expected " +
            std::to_string(CodecWireBytes(codec_, relems(i))) +
            "B encoded chunk, got " + std::to_string(got) +
            "B (ranks disagree on collective arguments, TPUNET_RING_CHUNKSIZE "
            "or TPUNET_WIRE_DTYPE?)"));
      }
    }
    if (i + 1 < n) {
      st = post(i + 1);  // keep the wire busy while we decode+reduce chunk i
      if (!st.ok()) return quiesce(st);
    }
    if (has_r) {
      if (fused_enc != nullptr) {
        // Chunks are block-aligned (CodecChunkElems), so the wire offset
        // of chunk i inside the whole-slice encoding is exact.
        DecodeReduceQuantize(codec_, acc_f + i * chunk_elems,
                                  loc_f + i * chunk_elems, rbuf(i),
                                  fused_enc + CodecWireBytes(codec_, i * chunk_elems),
                                  relems(i), wop);
      } else {
        DecodeReduce(codec_, acc_f + i * chunk_elems, loc_f + i * chunk_elems,
                          rbuf(i), relems(i), wop);
      }
    }
    if (i < ns) {
      st = WaitSend(sreq[slot]);
      slive[slot] = false;
      if (!st.ok()) return quiesce(st);
    }
  }
  return Status::Ok();
}

// Codec variant of the AllReduce AG phase ("AllGather passthrough":
// encode-only, no reduce). Slices travel ENCODED, and the encoded bytes
// are forwarded VERBATIM hop to hop while each rank decodes a private f32
// copy — so every rank materializes BIT-IDENTICAL values for every slice
// (the cross-rank determinism trainers assert on) and no hop ever
// re-quantizes. Precondition: the RS final round's fused handoff
// (CodecDecodeReduceQuantize) already QUANTIZED the owned slice in `data`
// and parked its encoded bytes in scratch slot 0 — what the owner keeps
// equals what every peer decodes, and this phase starts with zero codec
// passes of its own over the owned slice. Net effect: one quantization of
// each fully-reduced slice, on top of the RS phase's one-per-hop.
Status ScheduledCommunicator::AgPhaseCodec(float* data, size_t count, RingChannel& ch,
                                           uint64_t seq, bool tracing) {
  const int W = world_;
  auto off = [&](int i) { return (count * static_cast<size_t>(i)) / W; };
  const size_t max_elems = (count + W - 1) / W;
  const size_t slot_bytes = CodecWireBytes(codec_, max_elems);
  ch.scratch.reserve(2 * slot_bytes);  // no-op: DoAllReduceRing pre-reserved
  uint8_t* slots[2] = {ch.scratch.data(), ch.scratch.data() + slot_bytes};
  int cur = 0;  // slot 0 holds enc(owned slice), courtesy of the RS fusion
  for (int s = 0; s < W - 1; ++s) {
    int sidx = (rank_ - s + W) % W;
    int ridx = (rank_ - s - 1 + W) % W;
    size_t sw = CodecWireBytes(codec_, off(sidx + 1) - off(sidx));
    size_t relems = off(ridx + 1) - off(ridx);
    size_t rw = CodecWireBytes(codec_, relems);
    PhaseSpan step(tracing, trace_comm_id_, seq, "ag", s, sw);
    CountCollSteps(CollAlgo::kRing);
    // The slice sent at step s+1 is exactly the one received at step s
    // (sidx_{s+1} == ridx_s), so the received wire bytes ping-pong into
    // the next step's send slot untouched.
    Status st = Exchange(slots[cur], sw, slots[1 - cur], rw, nullptr, ch);
    if (!st.ok()) return st;
    CodecDecode(codec_, slots[1 - cur], data + off(ridx), relems);
    cur = 1 - cur;
  }
  return Status::Ok();
}

// One ring step: recv from prev into recvbuf while sending sendbuf to
// next. Posts the irecv first; BOTH requests are waited before returning —
// even on error — because an abandoned in-flight request would let the
// caller free a buffer the stream workers still touch. When got==nullptr
// the step is fixed-size and a short receive (ranks disagreeing on counts)
// is an error, not silent stale-tail corruption.
Status ScheduledCommunicator::Exchange(const void* sendbuf, size_t send_nbytes,
                                       void* recvbuf, size_t recv_nbytes,
                                       size_t* got, RingChannel& ch) {
  uint64_t rreq = 0;
  Status st = PostRecv(ch.recv_comm, recvbuf, recv_nbytes, &rreq);
  if (!st.ok()) return st;
  return ExchangePosted(rreq, sendbuf, send_nbytes, recv_nbytes, got, ch);
}

// The rest of a ring step whose recv `rreq` (of recv_nbytes) is posted: the
// send, then both waits, as Exchange.
Status ScheduledCommunicator::ExchangePosted(uint64_t rreq, const void* sendbuf,
                                             size_t send_nbytes, size_t recv_nbytes,
                                             size_t* got, RingChannel& ch) {
  uint64_t sreq = 0;
  Status st = PostSend(ch.send_comm, sendbuf, send_nbytes, &sreq);
  if (!st.ok()) {
    WaitRecv(rreq, nullptr);  // quiesce the posted recv before unwinding
    return st;
  }
  size_t rgot = 0;
  Status r_st = WaitRecv(rreq, &rgot);
  Status s_st = WaitSend(sreq);
  if (!r_st.ok()) return r_st;
  if (!s_st.ok()) return s_st;
  if (got) {
    *got = rgot;
  } else if (rgot != recv_nbytes) {
    return Status::Inner("ring step size mismatch: expected " + std::to_string(recv_nbytes) +
                         "B from prev rank, got " + std::to_string(rgot) +
                         "B (ranks disagree on collective arguments?)");
  }
  return Status::Ok();
}

// Wait out every pending send (ignoring their status) before surfacing
// `primary` — never abandon in-flight requests that reference caller
// buffers.
Status ScheduledCommunicator::DrainSends(std::vector<uint64_t>& reqs, Status primary) {
  for (uint64_t req : reqs) {
    Status st = WaitSend(req);
    if (primary.ok() && !st.ok()) primary = st;
  }
  reqs.clear();
  return primary;
}

}  // namespace internal
}  // namespace tpunet
