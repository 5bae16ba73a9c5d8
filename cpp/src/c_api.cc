// tpunet C ABI implementation. See c_api.h for the contract and the list of
// reference quirks deliberately fixed here (reference: src/lib.rs:19-392).
#include "tpunet/c_api.h"

#include <stdlib.h>
#include <string.h>

#include <algorithm>
#include <atomic>
#include <map>
#include <memory>
#include <vector>

#include "fault.h"
#include "flightrec.h"
#include "id_map.h"
#include "tpunet/mutex.h"
#include "tpunet/net.h"
#include "tpunet/qos.h"
#include "tpunet/telemetry.h"
#include "tpunet/utils.h"
#include "wire.h"

namespace {

using tpunet::Net;
using tpunet::NetProperties;
using tpunet::SocketHandle;
using tpunet::Status;

thread_local std::string g_last_error;

int32_t Fail(int32_t code, const std::string& msg) {
  g_last_error = msg;
  return code;
}

int32_t FromStatus(const Status& s) {
  if (s.ok()) return TPUNET_OK;
  switch (s.kind) {
    case tpunet::ErrorKind::kInvalidArgument:
      return Fail(TPUNET_ERR_INVALID, s.msg);
    case tpunet::ErrorKind::kCorruption:
      return Fail(TPUNET_ERR_CORRUPT, s.msg);
    case tpunet::ErrorKind::kTimeout:
      return Fail(TPUNET_ERR_TIMEOUT, s.msg);
    case tpunet::ErrorKind::kVersion:
      return Fail(TPUNET_ERR_VERSION, s.msg);
    case tpunet::ErrorKind::kCodec:
      return Fail(TPUNET_ERR_CODEC, s.msg);
    case tpunet::ErrorKind::kQosAdmission:
      return Fail(TPUNET_ERR_QOS_ADMISSION, s.msg);
    default:
      return Fail(TPUNET_ERR_INNER, s.msg);
  }
}

// An instance: the engine plus a property cache that owns the name/pci_path
// strings handed out through the ABI (reference kept a similar cache but
// freed Rust-allocated strings with C++ delete, cc/bagua_net.cc:8-31; here
// one allocator owns everything).
struct Instance {
  std::unique_ptr<Net> net;
  tpunet::Mutex props_mu;  // leaf lock
  // One cached entry per device, reused across calls — properties are static
  // per NIC, and reusing bounds the cache (a poll-properties loop must not
  // grow memory for the instance lifetime).
  std::map<int32_t, std::unique_ptr<NetProperties>> props_cache GUARDED_BY(props_mu);
};

tpunet::IdMap<std::shared_ptr<Instance>> g_instances;
std::atomic<uint64_t> g_next_instance_id{1};

std::shared_ptr<Instance> GetInstance(uintptr_t id) {
  std::shared_ptr<Instance> inst;
  g_instances.Get(id, &inst);
  return inst;
}

}  // namespace

extern "C" {

int32_t tpunet_c_create(uintptr_t* out_instance) {
  return tpunet_c_create_ex(nullptr, out_instance);
}

int32_t tpunet_c_create_ex(const char* traffic_class, uintptr_t* out_instance) {
  if (!out_instance) return Fail(TPUNET_ERR_NULL, "out_instance is null");
  tpunet::TrafficClass cls = tpunet::TrafficClass::kBulk;
  bool have_cls = traffic_class != nullptr && *traffic_class != '\0';
  if (have_cls && !tpunet::ParseTrafficClass(traffic_class, &cls)) {
    return Fail(TPUNET_ERR_INVALID,
                std::string("unknown traffic_class \"") + traffic_class +
                    "\" (expected latency, bulk or control)");
  }
  auto inst = std::make_shared<Instance>();
  inst->net = tpunet::CreateEngine();
  if (!inst->net) return Fail(TPUNET_ERR_INNER, "engine creation failed");
  if (have_cls) inst->net->set_traffic_class(static_cast<int32_t>(cls));
  uint64_t id = g_next_instance_id.fetch_add(1);
  g_instances.Put(id, inst);
  *out_instance = id;
  return TPUNET_OK;
}

int32_t tpunet_c_destroy(uintptr_t* instance) {
  if (!instance) return Fail(TPUNET_ERR_NULL, "instance is null");
  std::shared_ptr<Instance> inst;
  if (!g_instances.Take(*instance, &inst)) {
    return Fail(TPUNET_ERR_INVALID, "unknown instance");
  }
  *instance = 0;
  return TPUNET_OK;
}

int32_t tpunet_c_devices(uintptr_t instance, int32_t* ndev) {
  if (!ndev) return Fail(TPUNET_ERR_NULL, "ndev is null");
  auto inst = GetInstance(instance);
  if (!inst) return Fail(TPUNET_ERR_INVALID, "unknown instance");
  *ndev = inst->net->devices();
  return TPUNET_OK;
}

int32_t tpunet_c_get_properties(uintptr_t instance, int32_t dev,
                                tpunet_net_properties_t* props) {
  if (!props) return Fail(TPUNET_ERR_NULL, "props is null");
  auto inst = GetInstance(instance);
  if (!inst) return Fail(TPUNET_ERR_INVALID, "unknown instance");
  tpunet::MutexLock lk(inst->props_mu);
  auto it = inst->props_cache.find(dev);
  if (it == inst->props_cache.end()) {
    auto p = std::make_unique<NetProperties>();
    Status s = inst->net->get_properties(dev, p.get());
    if (!s.ok()) return FromStatus(s);
    it = inst->props_cache.emplace(dev, std::move(p)).first;
  }
  const NetProperties& p = *it->second;  // strings live until destroy
  props->name = p.name.c_str();
  props->pci_path = p.pci_path.c_str();
  props->guid = p.guid;
  props->ptr_support = p.ptr_support;
  props->speed_mbps = p.speed_mbps;
  props->port = p.port;
  props->max_comms = p.max_comms;
  return TPUNET_OK;
}

int32_t tpunet_c_listen(uintptr_t instance, int32_t dev,
                        tpunet_socket_handle_t* handle, uintptr_t* listen_comm) {
  if (!handle || !listen_comm) return Fail(TPUNET_ERR_NULL, "null out param");
  auto inst = GetInstance(instance);
  if (!inst) return Fail(TPUNET_ERR_INVALID, "unknown instance");
  SocketHandle h;
  uint64_t id = 0;
  Status s = inst->net->listen(dev, &h, &id);
  if (!s.ok()) return FromStatus(s);
  // Marshal: only the sockaddr bytes travel; length is derived from the
  // family on the far side (see basic_engine.cc AddrLenForFamily).
  memset(handle->data, 0, sizeof(handle->data));
  memcpy(handle->data, &h.addr, std::min(sizeof(handle->data), sizeof(h.addr)));
  *listen_comm = id;
  return TPUNET_OK;
}

int32_t tpunet_c_connect(uintptr_t instance, int32_t dev,
                         const tpunet_socket_handle_t* handle, uintptr_t* send_comm) {
  if (!handle || !send_comm) return Fail(TPUNET_ERR_NULL, "null param");
  auto inst = GetInstance(instance);
  if (!inst) return Fail(TPUNET_ERR_INVALID, "unknown instance");
  SocketHandle h;
  memcpy(&h.addr, handle->data, sizeof(handle->data));
  h.addrlen = 0;  // derived from family by the engine
  uint64_t id = 0;
  Status s = inst->net->connect(dev, h, &id);
  if (!s.ok()) return FromStatus(s);
  *send_comm = id;
  return TPUNET_OK;
}

int32_t tpunet_c_accept(uintptr_t instance, uintptr_t listen_comm, uintptr_t* recv_comm) {
  if (!recv_comm) return Fail(TPUNET_ERR_NULL, "recv_comm is null");
  auto inst = GetInstance(instance);
  if (!inst) return Fail(TPUNET_ERR_INVALID, "unknown instance");
  uint64_t id = 0;
  Status s = inst->net->accept(listen_comm, &id);
  if (!s.ok()) return FromStatus(s);
  *recv_comm = id;
  return TPUNET_OK;
}

int32_t tpunet_c_isend(uintptr_t instance, uintptr_t send_comm, const void* data,
                       uint64_t nbytes, uintptr_t* request) {
  if (!request || (nbytes > 0 && !data)) return Fail(TPUNET_ERR_NULL, "null param");
  auto inst = GetInstance(instance);
  if (!inst) return Fail(TPUNET_ERR_INVALID, "unknown instance");
  uint64_t id = 0;
  Status s = inst->net->isend(send_comm, data, nbytes, &id);
  if (!s.ok()) return FromStatus(s);
  *request = id;
  return TPUNET_OK;
}

int32_t tpunet_c_irecv(uintptr_t instance, uintptr_t recv_comm, void* data,
                       uint64_t nbytes, uintptr_t* request) {
  if (!request || (nbytes > 0 && !data)) return Fail(TPUNET_ERR_NULL, "null param");
  auto inst = GetInstance(instance);
  if (!inst) return Fail(TPUNET_ERR_INVALID, "unknown instance");
  uint64_t id = 0;
  Status s = inst->net->irecv(recv_comm, data, nbytes, &id);
  if (!s.ok()) return FromStatus(s);
  *request = id;
  return TPUNET_OK;
}

int32_t tpunet_c_test(uintptr_t instance, uintptr_t request, uint8_t* done,
                      uint64_t* nbytes) {
  if (!done) return Fail(TPUNET_ERR_NULL, "done is null");
  auto inst = GetInstance(instance);
  if (!inst) return Fail(TPUNET_ERR_INVALID, "unknown instance");
  bool d = false;
  size_t n = 0;
  Status s = inst->net->test(request, &d, &n);
  if (!s.ok()) return FromStatus(s);
  *done = d ? 1 : 0;
  if (nbytes) *nbytes = n;
  return TPUNET_OK;
}

int32_t tpunet_c_wait(uintptr_t instance, uintptr_t request, uint64_t* nbytes) {
  auto inst = GetInstance(instance);
  if (!inst) return Fail(TPUNET_ERR_INVALID, "unknown instance");
  size_t n = 0;
  Status s = inst->net->wait(request, &n);
  if (!s.ok()) return FromStatus(s);
  if (nbytes) *nbytes = n;
  return TPUNET_OK;
}

int32_t tpunet_c_close_send(uintptr_t instance, uintptr_t send_comm) {
  auto inst = GetInstance(instance);
  if (!inst) return Fail(TPUNET_ERR_INVALID, "unknown instance");
  return FromStatus(inst->net->close_send(send_comm));
}

int32_t tpunet_c_close_recv(uintptr_t instance, uintptr_t recv_comm) {
  auto inst = GetInstance(instance);
  if (!inst) return Fail(TPUNET_ERR_INVALID, "unknown instance");
  return FromStatus(inst->net->close_recv(recv_comm));
}

int32_t tpunet_c_close_listen(uintptr_t instance, uintptr_t listen_comm) {
  auto inst = GetInstance(instance);
  if (!inst) return Fail(TPUNET_ERR_INVALID, "unknown instance");
  return FromStatus(inst->net->close_listen(listen_comm));
}

const char* tpunet_c_last_error(void) { return g_last_error.c_str(); }

int32_t tpunet_c_fault_inject(const char* spec) {
  if (spec == nullptr || *spec == '\0') {
    tpunet::DisarmFault();
    return TPUNET_OK;
  }
  tpunet::FaultSpec f;
  bool has_fault = false;
  std::vector<tpunet::ChurnEvent> churn;
  std::vector<tpunet::SwapEvent> swap;
  Status s = tpunet::ParseFaultScript(spec, &f, &has_fault, &churn, &swap);
  if (!s.ok()) return FromStatus(s);
  if (has_fault) tpunet::ArmFault(f);
  if (!churn.empty()) tpunet::ArmChurnScript(churn);
  if (!swap.empty()) tpunet::ArmSwapScript(swap);
  return TPUNET_OK;
}

int32_t tpunet_c_fault_clear(void) {
  tpunet::DisarmFault();
  return TPUNET_OK;
}

int32_t tpunet_c_churn_poll(uint64_t step, int64_t rank) {
  return static_cast<int32_t>(tpunet::ChurnPoll(step, rank));
}

int32_t tpunet_c_churn_pending(void) { return tpunet::ChurnPending(); }

int32_t tpunet_c_swap_poll(uint64_t step) {
  return static_cast<int32_t>(tpunet::SwapPoll(step));
}

int32_t tpunet_c_swap_pending(void) { return tpunet::SwapPending(); }

uint32_t tpunet_c_crc32c(const void* data, uint64_t nbytes, uint32_t seed) {
  if (data == nullptr && nbytes > 0) return 0;
  return tpunet::Crc32c(data, static_cast<size_t>(nbytes), seed);
}

uint64_t tpunet_c_host_id(void) { return tpunet::HostId(); }

int32_t tpunet_c_reduce(void* dst, const void* a, const void* b, uint64_t n,
                        int32_t dtype, int32_t op) {
  if (dtype < 0 || dtype > 5) return Fail(TPUNET_ERR_INVALID, "bad dtype");
  if (op < 0 || op > 3) return Fail(TPUNET_ERR_INVALID, "bad op");
  if (n > 0 && (dst == nullptr || a == nullptr || b == nullptr)) {
    return Fail(TPUNET_ERR_INVALID, "null buffer with n > 0");
  }
  tpunet::ReduceInto(dst, a, b, static_cast<size_t>(n),
                     static_cast<tpunet::WireDType>(dtype),
                     static_cast<tpunet::WireRedOp>(op));
  return TPUNET_OK;
}

uint64_t tpunet_c_codec_wire_bytes(int32_t codec, uint64_t n) {
  if (codec < 0 || codec >= tpunet::kWireCodecCount) return 0;
  return tpunet::CodecWireBytes(static_cast<tpunet::WireCodec>(codec),
                                static_cast<size_t>(n));
}

int32_t tpunet_c_codec_encode(int32_t codec, const void* src, uint64_t n,
                              void* dst, uint64_t dst_cap) {
  if (codec < 0 || codec >= tpunet::kWireCodecCount) {
    return Fail(TPUNET_ERR_INVALID, "bad codec");
  }
  if (n > 0 && (src == nullptr || dst == nullptr)) {
    return Fail(TPUNET_ERR_NULL, "null buffer with n > 0");
  }
  auto c = static_cast<tpunet::WireCodec>(codec);
  if (dst_cap < tpunet::CodecWireBytes(c, static_cast<size_t>(n))) {
    return Fail(TPUNET_ERR_INVALID, "dst_cap smaller than the encoded size");
  }
  tpunet::CodecEncode(c, static_cast<const float*>(src),
                      static_cast<uint8_t*>(dst), static_cast<size_t>(n));
  return TPUNET_OK;
}

int32_t tpunet_c_codec_decode(int32_t codec, const void* wire, uint64_t n,
                              void* dst) {
  if (codec < 0 || codec >= tpunet::kWireCodecCount) {
    return Fail(TPUNET_ERR_INVALID, "bad codec");
  }
  if (n > 0 && (wire == nullptr || dst == nullptr)) {
    return Fail(TPUNET_ERR_NULL, "null buffer with n > 0");
  }
  tpunet::CodecDecode(static_cast<tpunet::WireCodec>(codec),
                      static_cast<const uint8_t*>(wire),
                      static_cast<float*>(dst), static_cast<size_t>(n));
  return TPUNET_OK;
}

}  // extern "C"

// ---- Collectives ABI ------------------------------------------------------

#include "tpunet/collectives.h"

namespace {

tpunet::IdMap<std::shared_ptr<tpunet::Communicator>> g_comms;
std::atomic<uint64_t> g_next_comm_id{1};

std::shared_ptr<tpunet::Communicator> GetComm(uintptr_t id) {
  std::shared_ptr<tpunet::Communicator> c;
  g_comms.Get(id, &c);
  return c;
}

bool ValidDType(int32_t d) { return d >= 0 && d <= 5; }
bool ValidOp(int32_t o) { return o >= 0 && o <= 3; }

// Process-default communicator id (0 = unset). The FFI custom-call
// collectives read it at call time so elastic recovery can swap the
// communicator under already-compiled executables.
std::atomic<uintptr_t> g_default_comm{0};

}  // namespace

extern "C" {

int32_t tpunet_comm_create(const char* coordinator, int32_t rank, int32_t world_size,
                           uintptr_t* comm) {
  return tpunet_comm_create_ex(coordinator, rank, world_size, nullptr, nullptr,
                               nullptr, comm);
}

int32_t tpunet_comm_create_ex(const char* coordinator, int32_t rank,
                              int32_t world_size, const char* wire_dtype,
                              const char* algo, const char* traffic_class,
                              uintptr_t* comm) {
  if (!coordinator || !comm) return Fail(TPUNET_ERR_NULL, "null param");
  std::unique_ptr<tpunet::Communicator> c;
  Status s = tpunet::Communicator::Create(coordinator, rank, world_size,
                                          wire_dtype ? wire_dtype : "",
                                          algo ? algo : "",
                                          traffic_class ? traffic_class : "",
                                          &c);
  if (!s.ok()) return FromStatus(s);
  uint64_t id = g_next_comm_id.fetch_add(1);
  g_comms.Put(id, std::shared_ptr<tpunet::Communicator>(std::move(c)));
  *comm = id;
  return TPUNET_OK;
}

int32_t tpunet_comm_wire_dtype(uintptr_t comm, int32_t* wire_dtype) {
  if (!wire_dtype) return Fail(TPUNET_ERR_NULL, "wire_dtype is null");
  auto c = GetComm(comm);
  if (!c) return Fail(TPUNET_ERR_INVALID, "unknown comm");
  *wire_dtype = c->wire_codec();
  return TPUNET_OK;
}

int32_t tpunet_comm_destroy(uintptr_t* comm) {
  if (!comm) return Fail(TPUNET_ERR_NULL, "comm is null");
  std::shared_ptr<tpunet::Communicator> c;
  if (!g_comms.Take(*comm, &c)) return Fail(TPUNET_ERR_INVALID, "unknown comm");
  // A destroyed comm must not remain the process default — a racing FFI
  // call would fetch a dead id (GetComm then fails loudly, but clear it
  // so the precondition error is the one callers see).
  uintptr_t expect = *comm;
  g_default_comm.compare_exchange_strong(expect, 0);
  *comm = 0;
  return TPUNET_OK;
}

int32_t tpunet_comm_set_default(uintptr_t comm) {
  if (comm != 0) {
    std::shared_ptr<tpunet::Communicator> c;
    if (!g_comms.Get(comm, &c)) return Fail(TPUNET_ERR_INVALID, "unknown comm");
  }
  g_default_comm.store(comm);
  return TPUNET_OK;
}

uintptr_t tpunet_comm_get_default(void) { return g_default_comm.load(); }

int32_t tpunet_comm_rank(uintptr_t comm, int32_t* rank, int32_t* world_size) {
  auto c = GetComm(comm);
  if (!c) return Fail(TPUNET_ERR_INVALID, "unknown comm");
  if (rank) *rank = c->rank();
  if (world_size) *world_size = c->world_size();
  return TPUNET_OK;
}

int32_t tpunet_comm_all_reduce(uintptr_t comm, const void* sendbuf, void* recvbuf,
                               uint64_t count, int32_t dtype, int32_t op) {
  if (count > 0 && (!sendbuf || !recvbuf)) return Fail(TPUNET_ERR_NULL, "null buffer");
  if (!ValidDType(dtype) || !ValidOp(op)) return Fail(TPUNET_ERR_INVALID, "bad dtype/op");
  auto c = GetComm(comm);
  if (!c) return Fail(TPUNET_ERR_INVALID, "unknown comm");
  return FromStatus(c->AllReduce(sendbuf, recvbuf, count, static_cast<tpunet::DType>(dtype),
                                 static_cast<tpunet::RedOp>(op)));
}

int32_t tpunet_comm_reduce_scatter(uintptr_t comm, const void* sendbuf, void* recvbuf,
                                   uint64_t recv_count, int32_t dtype, int32_t op) {
  if (recv_count > 0 && (!sendbuf || !recvbuf)) return Fail(TPUNET_ERR_NULL, "null buffer");
  if (!ValidDType(dtype) || !ValidOp(op)) return Fail(TPUNET_ERR_INVALID, "bad dtype/op");
  auto c = GetComm(comm);
  if (!c) return Fail(TPUNET_ERR_INVALID, "unknown comm");
  return FromStatus(c->ReduceScatter(sendbuf, recvbuf, recv_count,
                                     static_cast<tpunet::DType>(dtype),
                                     static_cast<tpunet::RedOp>(op)));
}

int32_t tpunet_comm_all_gather(uintptr_t comm, const void* sendbuf, void* recvbuf,
                               uint64_t bytes_per_rank) {
  if (bytes_per_rank > 0 && (!sendbuf || !recvbuf)) return Fail(TPUNET_ERR_NULL, "null buffer");
  auto c = GetComm(comm);
  if (!c) return Fail(TPUNET_ERR_INVALID, "unknown comm");
  return FromStatus(c->AllGather(sendbuf, recvbuf, bytes_per_rank));
}

int32_t tpunet_comm_broadcast(uintptr_t comm, void* buf, uint64_t nbytes, int32_t root) {
  if (nbytes > 0 && !buf) return Fail(TPUNET_ERR_NULL, "null buffer");
  auto c = GetComm(comm);
  if (!c) return Fail(TPUNET_ERR_INVALID, "unknown comm");
  return FromStatus(c->Broadcast(buf, nbytes, root));
}

int32_t tpunet_comm_all_to_all(uintptr_t comm, const void* sendbuf, void* recvbuf,
                               uint64_t bytes_per_rank) {
  if (bytes_per_rank > 0 && (!sendbuf || !recvbuf)) return Fail(TPUNET_ERR_NULL, "null buffer");
  auto c = GetComm(comm);
  if (!c) return Fail(TPUNET_ERR_INVALID, "unknown comm");
  return FromStatus(c->AllToAll(sendbuf, recvbuf, bytes_per_rank));
}

int32_t tpunet_comm_all_to_all_typed(uintptr_t comm, const void* sendbuf,
                                     void* recvbuf, uint64_t count_per_rank,
                                     int32_t dtype) {
  if (count_per_rank > 0 && (!sendbuf || !recvbuf)) {
    return Fail(TPUNET_ERR_NULL, "null buffer");
  }
  if (!ValidDType(dtype)) return Fail(TPUNET_ERR_INVALID, "bad dtype");
  auto c = GetComm(comm);
  if (!c) return Fail(TPUNET_ERR_INVALID, "unknown comm");
  return FromStatus(c->AllToAllTyped(sendbuf, recvbuf, count_per_rank,
                                     static_cast<tpunet::DType>(dtype)));
}

int32_t tpunet_comm_iall_to_all(uintptr_t comm, const void* sendbuf, void* recvbuf,
                                uint64_t bytes_per_rank, uint64_t* ticket) {
  if (!ticket || (bytes_per_rank > 0 && (!sendbuf || !recvbuf))) {
    return Fail(TPUNET_ERR_NULL, "null param");
  }
  auto c = GetComm(comm);
  if (!c) return Fail(TPUNET_ERR_INVALID, "unknown comm");
  return FromStatus(c->IAllToAll(sendbuf, recvbuf, bytes_per_rank, ticket));
}

int32_t tpunet_comm_neighbor_exchange(uintptr_t comm, const void* sendbuf,
                                      uint64_t send_nbytes, void* recvbuf,
                                      uint64_t recv_nbytes, uint64_t* got) {
  if ((send_nbytes > 0 && !sendbuf) || (recv_nbytes > 0 && !recvbuf)) {
    return Fail(TPUNET_ERR_NULL, "null buffer");
  }
  auto c = GetComm(comm);
  if (!c) return Fail(TPUNET_ERR_INVALID, "unknown comm");
  size_t g = 0;
  Status s = c->NeighborExchange(sendbuf, send_nbytes, recvbuf, recv_nbytes, &g);
  if (!s.ok()) return FromStatus(s);
  if (got) *got = g;
  return TPUNET_OK;
}

int32_t tpunet_comm_iall_reduce(uintptr_t comm, const void* sendbuf, void* recvbuf,
                                uint64_t count, int32_t dtype, int32_t op,
                                uint64_t* ticket) {
  if (!ticket || (count > 0 && (!sendbuf || !recvbuf))) {
    return Fail(TPUNET_ERR_NULL, "null param");
  }
  if (!ValidDType(dtype) || !ValidOp(op)) return Fail(TPUNET_ERR_INVALID, "bad dtype/op");
  auto c = GetComm(comm);
  if (!c) return Fail(TPUNET_ERR_INVALID, "unknown comm");
  return FromStatus(c->IAllReduce(sendbuf, recvbuf, count,
                                  static_cast<tpunet::DType>(dtype),
                                  static_cast<tpunet::RedOp>(op), ticket));
}

int32_t tpunet_comm_ticket_wait(uintptr_t comm, uint64_t ticket) {
  auto c = GetComm(comm);
  if (!c) return Fail(TPUNET_ERR_INVALID, "unknown comm");
  return FromStatus(c->WaitTicket(ticket));
}

int32_t tpunet_comm_ticket_test(uintptr_t comm, uint64_t ticket, uint8_t* done) {
  if (!done) return Fail(TPUNET_ERR_NULL, "done is null");
  auto c = GetComm(comm);
  if (!c) return Fail(TPUNET_ERR_INVALID, "unknown comm");
  bool d = false;
  Status s = c->TestTicket(ticket, &d);
  if (!s.ok()) return FromStatus(s);
  *done = d ? 1 : 0;
  return TPUNET_OK;
}

int32_t tpunet_comm_barrier(uintptr_t comm) {
  auto c = GetComm(comm);
  if (!c) return Fail(TPUNET_ERR_INVALID, "unknown comm");
  return FromStatus(c->Barrier());
}

int32_t tpunet_c_metrics_text(char* buf, uint64_t cap) {
  if (!buf && cap > 0) return Fail(TPUNET_ERR_NULL, "buf is null");
  std::string text = tpunet::Telemetry::Get().PrometheusText();
  if (cap > 0) {
    uint64_t n = std::min<uint64_t>(text.size(), cap - 1);
    memcpy(buf, text.data(), n);
    buf[n] = '\0';
  }
  return static_cast<int32_t>(text.size());
}

int32_t tpunet_c_metrics_reset(void) {
  tpunet::Telemetry::Get().Reset();
  return TPUNET_OK;
}

int32_t tpunet_c_trace_flush(void) {
  if (!tpunet::Telemetry::Get().FlushTrace()) {
    return Fail(TPUNET_ERR_INNER, "trace file unwritable; spans dropped");
  }
  return TPUNET_OK;
}

int32_t tpunet_c_trace_set_dir(const char* dir) {
  if (!tpunet::Telemetry::Get().SetTraceDir(dir ? dir : "")) {
    return Fail(TPUNET_ERR_INNER, "trace flush failed while retargeting");
  }
  return TPUNET_OK;
}

namespace {
// Names that go into the trace file verbatim: a closed alphabet, so no
// caller can break the file's JSON.
bool SpanNameOk(const char* s, bool may_be_empty) {
  if (!s || !*s) return may_be_empty;
  size_t n = 0;
  for (; s[n]; ++n) {
    const char c = s[n];
    const bool ok = (c >= 'a' && c <= 'z') || (c >= 'A' && c <= 'Z') ||
                    (c >= '0' && c <= '9') || c == '_' || c == '.' ||
                    c == ':' || c == '-';
    if (!ok || n >= 64) return false;
  }
  return true;
}
}  // namespace

int32_t tpunet_c_trace_span(const char* name, uint64_t start_us, uint64_t dur_us,
                            uint64_t seq, uint64_t nbytes, const char* parent,
                            const char* kind, int64_t step, int64_t chunk) {
  if (!SpanNameOk(name, false) || !SpanNameOk(parent, true) ||
      !SpanNameOk(kind, true)) {
    return Fail(TPUNET_ERR_INVALID,
                "span name/parent/kind must be 1-64 chars of [A-Za-z0-9_.:-]");
  }
  return tpunet::Telemetry::Get().OnProgramSpan(name, start_us, dur_us, seq,
                                                nbytes, parent, kind, step,
                                                chunk)
             ? 1
             : 0;
}

int32_t tpunet_c_bridge_call(int32_t kind, uint64_t nbytes) {
  if (kind < 0 || kind >= tpunet::kBridgeKindCount) {
    return Fail(TPUNET_ERR_INVALID,
                "kind must be 0..7 (all_reduce, all_reduce_start, "
                "all_reduce_finish, all_gather, reduce_scatter, all_to_all, "
                "broadcast, neighbor_exchange)");
  }
  tpunet::Telemetry::Get().OnBridgeCall(kind, nbytes);
  return TPUNET_OK;
}

int32_t tpunet_c_bridge_chunks(int32_t kind, uint64_t chunks, uint64_t in_flight) {
  if (kind < 0 || kind >= tpunet::kBridgeKindCount) {
    return Fail(TPUNET_ERR_INVALID, "kind must be 0..7 (as tpunet_c_bridge_call)");
  }
  tpunet::Telemetry::Get().OnBridgeChunks(kind, chunks, in_flight);
  return TPUNET_OK;
}

int32_t tpunet_c_bridge_minor_faults(int32_t kind, uint64_t faults) {
  if (kind < 0 || kind >= tpunet::kBridgeKindCount) {
    return Fail(TPUNET_ERR_INVALID, "kind must be 0..7 (as tpunet_c_bridge_call)");
  }
  tpunet::Telemetry::Get().OnBridgeMinorFaults(kind, faults);
  return TPUNET_OK;
}

int32_t tpunet_c_metrics_port(void) {
  return tpunet::Telemetry::Get().MetricsPort();
}

int32_t tpunet_c_serve_observe(int32_t kind, uint64_t us) {
  if (kind < 0 || kind > 1) {
    return Fail(TPUNET_ERR_INVALID, "kind must be 0 (ttft) or 1 (tpot)");
  }
  tpunet::Telemetry::Get().OnServeLatency(kind, us);
  return TPUNET_OK;
}

int32_t tpunet_c_serve_queue_depth(int32_t tier, uint64_t depth) {
  if (tier < 0 || tier >= tpunet::kServeTierCount) {
    return Fail(TPUNET_ERR_INVALID,
                "tier must be 0 (router), 1 (prefill) or 2 (decode)");
  }
  tpunet::Telemetry::Get().OnServeQueueDepth(tier, depth);
  return TPUNET_OK;
}

int32_t tpunet_c_rewire_observe(int32_t phase, uint64_t us) {
  if (phase < 0 || phase >= tpunet::kRewirePhaseCount) {
    return Fail(TPUNET_ERR_INVALID,
                "phase must be 0 (detect), 1 (quiesce), 2 (rendezvous) or "
                "3 (rewire)");
  }
  tpunet::Telemetry::Get().OnRewirePhase(phase, us);
  return TPUNET_OK;
}

int32_t tpunet_c_churn_event(int32_t kind) {
  if (kind < 0 || kind >= tpunet::kChurnKindCount) {
    return Fail(TPUNET_ERR_INVALID,
                "kind must be 0 (kill), 1 (join), 2 (shrink), 3 (grow) or "
                "4 (readmit)");
  }
  tpunet::Telemetry::Get().OnChurnEvent(kind);
  return TPUNET_OK;
}

int32_t tpunet_c_world_size(uint64_t world) {
  tpunet::Telemetry::Get().OnWorldSize(world);
  return TPUNET_OK;
}

int32_t tpunet_c_swap_observe(int32_t phase, uint64_t us) {
  if (phase < 0 || phase >= tpunet::kSwapPhaseCount) {
    return Fail(TPUNET_ERR_INVALID,
                "phase must be 0 (announce), 1 (broadcast), 2 (verify) or "
                "3 (flip)");
  }
  tpunet::Telemetry::Get().OnSwapPhase(phase, us);
  return TPUNET_OK;
}

int32_t tpunet_c_swap_event(int32_t kind) {
  if (kind < 0 || kind >= tpunet::kSwapKindCount) {
    return Fail(TPUNET_ERR_INVALID,
                "kind must be 0 (publish), 1 (commit), 2 (abort), 3 (retry) "
                "or 4 (mismatch)");
  }
  tpunet::Telemetry::Get().OnSwapEvent(kind);
  return TPUNET_OK;
}

int32_t tpunet_c_weight_version(uint64_t version) {
  tpunet::Telemetry::Get().OnWeightVersion(version);
  return TPUNET_OK;
}

int32_t tpunet_c_flightrec_dump(const char* dir, const char* reason,
                                char* out_path, uint64_t cap) {
  if (!out_path && cap > 0) return Fail(TPUNET_ERR_NULL, "out_path is null");
  // The ring initializes lazily on first Record; an on-demand dump before
  // any traffic must still produce a (header-only) file.
  if (tpunet::flightrec::internal::InitRing() == nullptr) {
    return Fail(TPUNET_ERR_INVALID,
                "flight recorder disabled (TPUNET_FLIGHTREC_EVENTS=0)");
  }
  // The reason lands verbatim inside a JSON string in the dump header:
  // sanitize the caller-supplied text instead of trusting it.
  char clean[64];
  const char* src = reason != nullptr && reason[0] != '\0' ? reason : "api";
  size_t n = 0;
  for (; src[n] != '\0' && n < sizeof(clean) - 1; ++n) {
    char ch = src[n];
    bool ok = (ch >= 'a' && ch <= 'z') || (ch >= 'A' && ch <= 'Z') ||
              (ch >= '0' && ch <= '9') || ch == '_' || ch == '-' ||
              ch == '.' || ch == ' ' || ch == ':';
    clean[n] = ok ? ch : '_';
  }
  clean[n] = '\0';
  int len = tpunet::flightrec::Dump(dir, clean, out_path, cap);
  if (len <= 0) {
    return Fail(TPUNET_ERR_INVALID, "flight recorder dump target unwritable");
  }
  return len;
}

int32_t tpunet_c_flightrec_stats(uint64_t* recorded, uint64_t* capacity) {
  tpunet::flightrec::Stats(recorded, capacity);
  return TPUNET_OK;
}

int32_t tpunet_c_lane_parse(const char* spec, char* out, uint64_t cap) {
  if ((!out && cap > 0) || !spec) return Fail(TPUNET_ERR_NULL, "null param");
  std::vector<tpunet::LaneSpec> lanes;
  Status s = tpunet::ParseLaneSpec(spec, &lanes);
  if (!s.ok()) return FromStatus(s);
  std::string text;
  for (size_t i = 0; i < lanes.size(); ++i) {
    text += "lane=" + std::to_string(i) + " addr=" +
            (lanes[i].addr.empty() ? "-" : lanes[i].addr) +
            " w=" + std::to_string(lanes[i].weight) + "\n";
  }
  if (cap > 0) {
    uint64_t n = std::min<uint64_t>(text.size(), cap - 1);
    memcpy(out, text.data(), n);
    out[n] = '\0';
  }
  return static_cast<int32_t>(text.size());
}

int32_t tpunet_c_stripe_map(uint64_t len, uint64_t min_chunksize,
                            const char* weights, uint64_t cursor, char* out,
                            uint64_t cap) {
  if ((!out && cap > 0) || !weights) return Fail(TPUNET_ERR_NULL, "null param");
  if (min_chunksize == 0) return Fail(TPUNET_ERR_INVALID, "min_chunksize must be >= 1");
  std::vector<uint32_t> w;
  std::string tok;
  std::string spec(weights);
  for (size_t pos = 0; pos <= spec.size(); ++pos) {
    if (pos < spec.size() && spec[pos] != ',') {
      tok += spec[pos];
      continue;
    }
    if (tok.empty()) return Fail(TPUNET_ERR_INVALID, "empty weight in list");
    char* end = nullptr;
    unsigned long v = strtoul(tok.c_str(), &end, 10);
    if ((end && *end != '\0') || v < 1 || v > 255) {
      return Fail(TPUNET_ERR_INVALID, "weight \"" + tok + "\" must be 1..255");
    }
    w.push_back(static_cast<uint32_t>(v));
    tok.clear();
  }
  if (w.empty() || w.size() > 256) {
    return Fail(TPUNET_ERR_INVALID, "weight list must name 1..256 streams");
  }
  // Exactly the engines' derivation: shared chunk math, then the WRR
  // slot-table walk from the cursor (uniform weights degenerate to
  // cursor % nstreams — the pre-lane rotation).
  size_t csize = tpunet::ChunkSize(len, min_chunksize, w.size());
  size_t nchunks = tpunet::ChunkCount(len, csize);
  std::vector<uint8_t> slots = tpunet::BuildWrrSlots(w);
  std::string text;
  for (size_t i = 0; i < nchunks; ++i) {
    if (i) text += ",";
    text += std::to_string(slots[(cursor + i) % slots.size()]);
  }
  if (cap > 0) {
    uint64_t n = std::min<uint64_t>(text.size(), cap - 1);
    memcpy(out, text.data(), n);
    out[n] = '\0';
  }
  return static_cast<int32_t>(text.size());
}

int32_t tpunet_c_qos_state(char* buf, uint64_t cap) {
  if (!buf && cap > 0) return Fail(TPUNET_ERR_NULL, "buf is null");
  std::string text = tpunet::QosScheduler::Get().StateText();
  if (cap > 0) {
    uint64_t n = std::min<uint64_t>(text.size(), cap - 1);
    memcpy(buf, text.data(), n);
    buf[n] = '\0';
  }
  return static_cast<int32_t>(text.size());
}

int32_t tpunet_c_qos_drr_golden(const char* weights, const char* window,
                                const char* chunks, char* out, uint64_t cap) {
  if ((!out && cap > 0) || !chunks) return Fail(TPUNET_ERR_NULL, "null param");
  std::string err;
  std::string order = tpunet::QosScheduler::DrrGolden(
      weights ? weights : "", window ? window : "", chunks, &err);
  if (!err.empty()) return Fail(TPUNET_ERR_INVALID, err);
  if (cap > 0) {
    uint64_t n = std::min<uint64_t>(order.size(), cap - 1);
    memcpy(out, order.data(), n);
    out[n] = '\0';
  }
  return static_cast<int32_t>(order.size());
}

}  // extern "C"
