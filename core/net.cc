#include "net.h"

#include <arpa/inet.h>
#include <dirent.h>
#include <fcntl.h>
#include <netinet/in.h>
#include <netinet/tcp.h>
#include <poll.h>
#ifdef __linux__
#include <sys/epoll.h>
#include <sys/stat.h>
#endif
#include <sys/socket.h>
#include <unistd.h>

#include <algorithm>
#include <cerrno>
#include <cmath>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <type_traits>

#include "ed25519.h"
#include "flight.h"
#include "net_shard.h"
#include "verify_pool.h"

namespace pbft {

void tune_stream_socket(int fd) {
  int one = 1;
  setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
}

void tune_listen_socket(int fd) {
  int one = 1;
  setsockopt(fd, SOL_SOCKET, SO_REUSEADDR, &one, sizeof(one));
}

namespace {

void set_nonblocking(int fd) {
  int flags = fcntl(fd, F_GETFL, 0);
  fcntl(fd, F_SETFL, flags | O_NONBLOCK);
}

bool split_host_port(const std::string& hp, std::string* host, int* port) {
  auto pos = hp.rfind(':');
  if (pos == std::string::npos) return false;
  *host = hp.substr(0, pos);
  *port = std::atoi(hp.c_str() + pos + 1);
  return *port > 0;
}

}  // namespace

namespace {
// Shared dial prologue: resolve, create, (optionally) set nonblocking,
// connect. One copy so address handling cannot drift between the
// blocking and nonblocking dialers.
int dial_socket(const std::string& host_port, bool nonblocking,
                bool* in_progress) {
  if (in_progress) *in_progress = false;
  std::string host;
  int port;
  if (!split_host_port(host_port, &host, &port)) return -1;
  int fd = socket(AF_INET, SOCK_STREAM, 0);
  if (fd < 0) return -1;
  tune_stream_socket(fd);
  if (nonblocking) set_nonblocking(fd);
  sockaddr_in addr{};
  addr.sin_family = AF_INET;
  addr.sin_port = htons((uint16_t)port);
  if (inet_pton(AF_INET, host.c_str(), &addr.sin_addr) != 1) {
    close(fd);
    return -1;
  }
  if (connect(fd, (sockaddr*)&addr, sizeof(addr)) != 0) {
    if (!nonblocking || errno != EINPROGRESS) {
      close(fd);
      return -1;
    }
    if (in_progress) *in_progress = true;
  }
  return fd;
}
}  // namespace

// -- readiness backends (ISSUE 10 tentpole) ---------------------------------

namespace {

// Portable fallback (and the PBFT_NET_POLL=1 parity lever): a persistent
// pollfd table maintained incrementally — add appends, remove
// swap-erases, write interest flips one events field. O(1) each via the
// fd index map; never rebuilt per iteration.
class PollPoller : public Poller {
 public:
  const char* name() const override { return "poll"; }

  bool add(int fd, uint64_t tag, bool /*edge*/) override {
    index_[fd] = pfds_.size();
    pfds_.push_back({fd, POLLIN, 0});
    tags_.push_back(tag);
    return true;
  }

  void remove(int fd) override {
    auto it = index_.find(fd);
    if (it == index_.end()) return;
    size_t i = it->second;
    index_.erase(it);
    size_t last = pfds_.size() - 1;
    if (i != last) {
      pfds_[i] = pfds_[last];
      tags_[i] = tags_[last];
      index_[pfds_[i].fd] = i;
    }
    pfds_.pop_back();
    tags_.pop_back();
  }

  void set_write_interest(int fd, bool want) override {
    auto it = index_.find(fd);
    if (it == index_.end()) return;
    pfds_[it->second].events = (short)(POLLIN | (want ? POLLOUT : 0));
  }

  int wait(std::vector<PollerEvent>* out, int timeout_ms) override {
    int n = ::poll(pfds_.data(), (nfds_t)pfds_.size(), timeout_ms);
    if (n <= 0) return n;
    for (size_t i = 0; i < pfds_.size(); ++i) {
      short re = pfds_[i].revents;
      if (!re) continue;
      out->push_back({tags_[i], (re & (POLLIN | POLLHUP | POLLERR)) != 0,
                      (re & POLLOUT) != 0,
                      (re & (POLLERR | POLLHUP | POLLNVAL)) != 0});
    }
    return n;
  }

 private:
  std::vector<pollfd> pfds_;
  std::vector<uint64_t> tags_;
  std::map<int, size_t> index_;
};

#ifdef __linux__
// Edge-triggered epoll: connections register EPOLLIN|EPOLLOUT|EPOLLET
// ONCE and are never re-armed — reads drain to EAGAIN, writes flush
// eagerly at enqueue, and an EPOLLOUT edge resumes a partially-written
// queue when the kernel buffer empties. Sentinel fds (listener, metrics,
// verifier stream) stay level-triggered: their handlers do bounded work
// per event and partial reads must re-fire.
class EpollPoller : public Poller {
 public:
  EpollPoller() : epfd_(epoll_create1(EPOLL_CLOEXEC)) {}
  ~EpollPoller() override {
    if (epfd_ >= 0) close(epfd_);
  }
  bool ok() const { return epfd_ >= 0; }
  const char* name() const override { return "epoll-et"; }

  bool add(int fd, uint64_t tag, bool edge) override {
    epoll_event ev{};
    ev.events = edge ? (EPOLLIN | EPOLLOUT | EPOLLET) : EPOLLIN;
    ev.data.u64 = tag;
    return epoll_ctl(epfd_, EPOLL_CTL_ADD, fd, &ev) == 0;
  }

  void remove(int fd) override {
    // EBADF/ENOENT are expected when the fd already closed (the kernel
    // auto-deregisters closed fds) — removal is best-effort by design.
    epoll_event ev{};
    (void)epoll_ctl(epfd_, EPOLL_CTL_DEL, fd, &ev);
  }

  void set_write_interest(int /*fd*/, bool /*want*/) override {}

  int wait(std::vector<PollerEvent>* out, int timeout_ms) override {
    epoll_event evs[256];
    int n = epoll_wait(epfd_, evs, 256, timeout_ms);
    for (int i = 0; i < n; ++i) {
      uint32_t e = evs[i].events;
      out->push_back({evs[i].data.u64,
                      (e & (EPOLLIN | EPOLLHUP | EPOLLERR)) != 0,
                      (e & EPOLLOUT) != 0, (e & (EPOLLERR | EPOLLHUP)) != 0});
    }
    return n;
  }

 private:
  int epfd_;
};
#endif  // __linux__

}  // namespace

std::unique_ptr<Poller> make_poller() {
#ifdef __linux__
  const char* force = std::getenv("PBFT_NET_POLL");
  if (force == nullptr || *force == '\0' || *force == '0') {
    auto ep = std::make_unique<EpollPoller>();
    if (ep->ok()) return ep;
  }
#endif
  return std::make_unique<PollPoller>();
}

namespace {
// Poller sentinel tags for non-Conn fds (heap pointers are aligned and
// never collide with these small values).
constexpr uint64_t kTagListener = 1;
constexpr uint64_t kTagMetrics = 2;
constexpr uint64_t kTagVerifier = 3;
// Multi-core mode (ISSUE 13): the shard->consensus inbox wake fd.
constexpr uint64_t kTagShardWake = 4;

// Bounded outbound queue per connection (ISSUE 10 satellite): past this,
// frames are dropped and counted instead of growing without limit
// against a slow or black-holed reader. 8 MiB ≈ thousands of protocol
// frames — far beyond what retransmission-covered loss can justify
// buffering.
constexpr size_t kMaxConnOutbound = 8u << 20;
// Coalescing target for send blocks: frames pack into pooled blocks of
// about this size so one send() carries many frames.
constexpr size_t kMaxSendBlock = 64u << 10;
// Gateway route-cache bound: on overflow the cache CLEARS and un-routed
// "gw/" replies fan out over all gateway links until re-registration —
// extra frames, never lost quorums.
constexpr size_t kMaxGatewayRoutes = 1u << 17;
}  // namespace

// Shared with the shard/pipeline tier (core/net_shard.cc); the values
// stay declared above so the constants lint keeps reading them here.
size_t max_conn_outbound() { return kMaxConnOutbound; }
size_t max_send_block() { return kMaxSendBlock; }

const char* ReplicaServer::net_backend() const { return poller_->name(); }

// The shared MAC-vector frame for a broadcast (ISSUE 14): one lane per
// dest in the sender's key table, all over one signable digest. Defined
// here (not net.h) so the header stays crypto-free.
const std::string* EncodedOut::mac_payload(
    const std::map<int64_t, std::array<uint8_t, 32>>& keys) {
  if (!mac_tried) {
    mac_tried = true;
    if (!keys.empty()) {
      uint8_t signable[32];
      message_signable(*m, signable);
      std::vector<MacLane> lanes;
      lanes.reserve(keys.size());
      for (const auto& [rid, key] : keys) {  // std::map: sorted lanes
        MacLane lane;
        lane.rid = rid;
        mac_tag(key.data(), signable, lane.tag);
        lanes.push_back(lane);
      }
      mac_ok = message_to_binary_mac(*m, lanes, &mac);
      if (mac_ok) ++encodes;
    }
  }
  return mac_ok ? &mac : nullptr;
}


bool fault_mode_from_string(const std::string& s, FaultMode* out) {
  if (s.empty() || s == "none") *out = FaultMode::kNone;
  else if (s == "sig-corrupt" || s == "byzantine") *out = FaultMode::kSigCorrupt;
  else if (s == "mute") *out = FaultMode::kMute;
  else if (s == "stutter") *out = FaultMode::kStutter;
  else if (s == "equivocate") *out = FaultMode::kEquivocate;
  else return false;
  return true;
}

int dial_tcp(const std::string& host_port) {
  return dial_socket(host_port, /*nonblocking=*/false, nullptr);
}

int dial_tcp_nb(const std::string& host_port, bool* in_progress) {
  return dial_socket(host_port, /*nonblocking=*/true, in_progress);
}

ReplicaServer::ReplicaServer(ClusterConfig cfg, int64_t id,
                             const uint8_t seed[32],
                             std::unique_ptr<Verifier> verifier)
    : cfg_(cfg), id_(id), verifier_(std::move(verifier)) {
  std::memcpy(seed_, seed, 32);
  // Fast-path offer (ISSUE 14): config asks, the env levers may cap it.
  fastpath_mac_ = wire_offer_mac(cfg_.fastpath == "mac");
  // Readiness backend before any conn can exist: every accept/dial path
  // registers with the poller unconditionally.
  poller_ = make_poller();
  replica_ = std::make_unique<Replica>(cfg_, id_, seed);
  // Consensus-phase spans: the hook costs one branch inside on_phase when
  // neither metrics nor tracing is active (the Tracer discipline).
  replica_->phase_hook = [this](const char* phase, int64_t view,
                                int64_t seq) { on_phase(phase, view, seq); };
  // Batch occupancy at every pre-prepare accept (ISSUE 4).
  replica_->batch_hook = [this](int64_t n) {
    metrics_.observe("pbft_batch_size", (double)n);
  };
  // How long a tentative execution stays revocable (ISSUE 32).
  replica_->commit_hook = [this](int64_t seq) { on_commit_floor(seq); };
  // View-change spans (ISSUE 9): rare events, stamped into trace lines
  // + the flight recorder by on_view_event.
  replica_->view_hook = [this](const char* ev, int64_t v) {
    on_view_event(ev, v);
  };
}

ReplicaServer::~ReplicaServer() {
  // Multi-core mode: the shard/pipeline threads reference this object's
  // config/seed and queues — stop and join them before anything tears
  // down (stop_join sets stopping_ and wakes every thread).
  if (shards_) shards_->stop_join();
  if (trace_fp_) std::fclose(trace_fp_);
  if (listen_fd_ >= 0) close(listen_fd_);
  if (metrics_listen_fd_ >= 0) close(metrics_listen_fd_);
  for (auto& c : conns_)
    if (c->fd >= 0) close(c->fd);
  for (auto& [_, c] : peers_)
    if (c->fd >= 0) close(c->fd);
}

bool ReplicaServer::start() {
  if (cfg_.net_threads > 1) {
    // Multi-core front end (ISSUE 13): N loop shards own the listeners
    // (SO_REUSEPORT accept sharding) and every data socket; this thread
    // keeps only the metrics listener, the verifier stream, and the
    // shard-inbox wake fd on its poller.
    shards_ = std::make_unique<NetShards>(cfg_, id_, seed_, &stopping_,
                                          (int)cfg_.net_threads);
    shards_->set_chaos(chaos_drop_pct_, chaos_delay_ms_, chaos_seed_);
    if (!shards_->start(&listen_port_)) return false;
    poller_->add(shards_->wake_fd(), kTagShardWake, /*edge=*/false);
    metrics_.set_gauge("pbft_net_loop_threads",
                       (double)shards_->n_shards());
  } else {
    metrics_.set_gauge("pbft_net_loop_threads", 1.0);
    listen_fd_ = socket(AF_INET, SOCK_STREAM, 0);
    if (listen_fd_ < 0) return false;
    tune_listen_socket(listen_fd_);
    sockaddr_in addr{};
    addr.sin_family = AF_INET;
    addr.sin_addr.s_addr = htonl(INADDR_ANY);
    addr.sin_port = htons((uint16_t)cfg_.replicas[id_].port);
    if (bind(listen_fd_, (sockaddr*)&addr, sizeof(addr)) != 0) return false;
    if (listen(listen_fd_, 128) != 0) return false;
    socklen_t len = sizeof(addr);
    getsockname(listen_fd_, (sockaddr*)&addr, &len);
    listen_port_ = ntohs(addr.sin_port);
    set_nonblocking(listen_fd_);
    poller_->add(listen_fd_, kTagListener, /*edge=*/false);
  }
  if (metrics_port_ >= 0) {
    metrics_listen_fd_ = socket(AF_INET, SOCK_STREAM, 0);
    sockaddr_in maddr{};
    maddr.sin_family = AF_INET;
    maddr.sin_addr.s_addr = htonl(INADDR_LOOPBACK);
    maddr.sin_port = htons((uint16_t)metrics_port_);
    if (metrics_listen_fd_ >= 0) tune_listen_socket(metrics_listen_fd_);
    if (metrics_listen_fd_ < 0 ||
        bind(metrics_listen_fd_, (sockaddr*)&maddr, sizeof(maddr)) != 0 ||
        listen(metrics_listen_fd_, 16) != 0) {
      std::fprintf(stderr, "replica %lld: metrics bind failed on port %d\n",
                   (long long)id_, metrics_port_);
      if (metrics_listen_fd_ >= 0) close(metrics_listen_fd_);
      metrics_listen_fd_ = -1;
    } else {
      socklen_t mlen = sizeof(maddr);
      getsockname(metrics_listen_fd_, (sockaddr*)&maddr, &mlen);
      metrics_listen_port_ = ntohs(maddr.sin_port);
      set_nonblocking(metrics_listen_fd_);
      poller_->add(metrics_listen_fd_, kTagMetrics, /*edge=*/false);
      metrics_.enabled = true;
      // enable_wal ran before the registry existed (recovery must
      // precede networking): backfill its gauge now (ISSUE 15).
      metrics_.set_gauge("pbft_recovery_seconds", recovery_seconds_);
    }
  }
  if (!discovery_target_.empty()) {
    discovery_ =
        std::make_unique<Discovery>(discovery_target_, id_, listen_port_,
                                    cfg_.n());
    if (!discovery_->start()) {
      std::fprintf(stderr, "replica %lld: discovery on %s failed\n",
                   (long long)id_, discovery_target_.c_str());
      discovery_.reset();
    } else {
      discovery_->announce();
    }
  }
  return true;
}

void ReplicaServer::run() {
  while (!stopping_) poll_once(100);
}

void ReplicaServer::poll_once(int timeout_ms) {
  // A pass starts (and ends) in the loop clock's `other` stage; the work
  // below switches it by kind, nested (net.h LoopClock).
  loop_clock_.set_on(metrics_.enabled || trace_fp_ != nullptr);
  if (verify_window_open_) {
    // An open accumulation window caps how long we may sit in poll():
    // the flush deadline is a latency promise, not a hint.
    auto deadline =
        verify_window_start_ + std::chrono::microseconds(cfg_.verify_flush_us);
    auto rem = std::chrono::duration_cast<std::chrono::milliseconds>(
                   deadline - std::chrono::steady_clock::now())
                   .count();
    timeout_ms = std::min<int64_t>(timeout_ms, std::max<int64_t>(rem, 0) + 1);
  }
  if (batch_window_open_) {
    // A partial request batch is waiting: the batch_flush_us deadline is
    // a latency promise too — don't sleep past it.
    auto deadline =
        batch_window_start_ + std::chrono::microseconds(cfg_.batch_flush_us);
    auto rem = std::chrono::duration_cast<std::chrono::milliseconds>(
                   deadline - std::chrono::steady_clock::now())
                   .count();
    timeout_ms = std::min<int64_t>(timeout_ms, std::max<int64_t>(rem, 0) + 1);
  }
  if (!chaos_queue_.empty()) {
    // Held (chaos-delayed) frames release on a deadline; a quiet socket
    // set must not stretch the injected delay past what was drawn.
    auto earliest = std::chrono::steady_clock::time_point::max();
    for (const auto& [_, q] : chaos_queue_) {
      if (!q.empty()) earliest = std::min(earliest, q.front().first);
    }
    if (earliest != std::chrono::steady_clock::time_point::max()) {
      auto rem = std::chrono::duration_cast<std::chrono::milliseconds>(
                     earliest - std::chrono::steady_clock::now())
                     .count();
      timeout_ms =
          std::min<int64_t>(timeout_ms, std::max<int64_t>(rem, 0) + 1);
    }
  }
  if (verify_inflight_ && verify_deadline_ms_ > 0) {
    // Don't let a quiet cluster sleep past the wedge deadline.
    auto rem = std::chrono::duration_cast<std::chrono::milliseconds>(
                   inflight_start_ +
                   std::chrono::milliseconds(verify_deadline_ms_) -
                   std::chrono::steady_clock::now())
                   .count();
    timeout_ms = std::min<int64_t>(timeout_ms, std::max<int64_t>(rem, 0) + 1);
  }
  if (connecting_count_ > 0) {
    // Nonblocking dials in flight: wake often enough that the sweep
    // reaps an overdue connect within ~100 ms of its deadline.
    timeout_ms = std::min(timeout_ms, 100);
  }
  // Persistent registrations: conns/listeners/the verifier stream were
  // registered at creation — the wait is one syscall over the backend's
  // standing table, no per-iteration pollfd rebuild.
  events_.clear();
  int n;
  {
    LoopClock::Scope in(loop_clock_, kLoopWait);
    n = poller_->wait(&events_, timeout_ms);
  }
  if (n < 0) return;
  ++event_wakeups_;  // pbft_epoll_wakeups_total: folded at the scrape
  for (const PollerEvent& ev : events_) {
    if (ev.tag == kTagListener) {
      if (ev.readable) accept_ready();
      continue;
    }
    if (ev.tag == kTagMetrics) {
      if (ev.readable) serve_metrics_ready();
      continue;
    }
    if (ev.tag == kTagVerifier) {
      // Async verifier verdict readiness is just another I/O event.
      if (verify_inflight_ && (ev.readable || ev.error)) {
        finish_verify_async();
      }
      continue;
    }
    if (ev.tag == kTagShardWake) {
      // Multi-core mode: parsed messages (and gateway-link lifecycle)
      // from the crypto pipelines. Level-triggered: readable persists
      // until the inbox drains, so a wake is never lost.
      if (ev.readable) process_shard_inbound();
      continue;
    }
    Conn* c = reinterpret_cast<Conn*>((uintptr_t)ev.tag);
    // A conn closed earlier THIS iteration still owns its (stale) event:
    // the object lives until the end-of-pass sweep, so the flag check is
    // safe — and fd reuse cannot alias it, closed fds left the poller.
    if (c->closed) continue;
    if (c->connecting) {
      if (ev.writable || ev.error) finish_connect(*c);
      continue;
    }
    if (ev.readable || ev.error) handle_readable(*c);
    if (ev.writable && !c->closed) flush(*c);
  }
  check_verify_deadline(std::chrono::steady_clock::now());
  // Seal a partial request batch once it has waited its flush window
  // (ISSUE 4) — BEFORE the verify batch, so the resulting pre-prepare's
  // self-delivered protocol messages ride this pass's verifier launch.
  check_batch_flush(std::chrono::steady_clock::now());
  // The batching window: everything that arrived this iteration verifies
  // as one batch (one XLA launch on the TPU backend). The order of a pass
  // with an async verifier: the verifier's event above only READ the
  // verdicts of the batch that came back and kept them; here the span of
  // the inbox behind that batch (what accumulated during its trip, this
  // pass's reads included) is launched FIRST, and only then are the kept
  // verdicts worked through — dispatch, execute, sign, WAL flush, sends —
  // while the next trip is already under way. One batch on the wire, one
  // span of verdicts kept, and spans are delivered in inbox order.
  run_verify_batch();
  // Group-commit straggler sweep (ISSUE 15): emit() already flushed
  // before its sends; this covers records noted on paths that produced
  // no actions this pass. No-op when nothing pends.
  if (wal_) flush_wal();
  pump_chaos_queue(std::chrono::steady_clock::now());  // release held frames
  pump_reply_backlog();  // launch queued reply dials as slots free
  aggregate_shard_metrics();  // multi-core mode: fold shard counters in
  check_progress_timer();
  if (discovery_) {
    discovery_->poll(&discovered_addrs_);
    auto now = std::chrono::steady_clock::now();
    if (now - last_beacon_ > std::chrono::seconds(1)) {
      discovery_->announce();
      last_beacon_ = now;
    }
  }
  sweep_conns();
}

// Reap overdue nonblocking connects, drop closed conns (their pooled
// buffers return to the pool), refresh the connecting count and the
// connections-open gauge. Runs once per iteration AFTER event dispatch —
// a Conn closed mid-pass must outlive any stale event referencing it.
void ReplicaServer::sweep_conns() {
  if (shards_) {
    // Multi-core mode: sweep bookkeeping is per-shard (each shard reaps
    // its own overdue connects — the ISSUE 13 satellite); this thread
    // only refreshes the aggregate gauge.
    metrics_.set_gauge("pbft_connections_open",
                       (double)shards_->connections_open());
    return;
  }
  const auto now = std::chrono::steady_clock::now();
  connecting_count_ = 0;
  auto visit = [&](Conn& c) {
    if (!c.closed && c.connecting) {
      // Reap dials that never complete (black-holed address): the
      // deadline bounds how long a one-shot reply or peer link can sit.
      if (now > c.connect_deadline) {
        mark_closed(c);
      } else {
        ++connecting_count_;
      }
    }
  };
  for (auto& c : conns_) visit(*c);
  for (auto& [_, c] : peers_) visit(*c);
  conns_.erase(
      std::remove_if(conns_.begin(), conns_.end(),
                     [](const std::unique_ptr<Conn>& c) { return c->closed; }),
      conns_.end());
  for (auto it = peers_.begin(); it != peers_.end();) {
    if (it->second->closed) {
      it = peers_.erase(it);
    } else {
      ++it;
    }
  }
  metrics_.set_gauge("pbft_connections_open",
                     (double)(conns_.size() + peers_.size()));
}

// Pack a shard-owned gateway link into one route-table key (shard index
// in the top bits, the shard-local conn token below). Shard counts are
// tiny and tokens monotonically count accepted conns — 48 bits is years
// of churn.
namespace {
inline uint64_t shard_link_key(int shard, uint64_t conn_id) {
  return ((uint64_t)shard << 48) | (conn_id & ((1ull << 48) - 1));
}
}  // namespace

void ReplicaServer::process_shard_inbound() {
  LoopClock::Scope in_read(loop_clock_, kLoopRead);
  std::deque<KInbound> in;
  auto oldest = std::chrono::steady_clock::time_point::max();
  shards_->drain_inbox(&in, &oldest);
  if (metrics_.enabled &&
      oldest != std::chrono::steady_clock::time_point::max()) {
    // The hand-off's latency, once a drain that found something: this
    // drain's instant minus the push of the OLDEST entry it took (the
    // pipeline stamps a push that finds its queue empty).
    const double waited = std::chrono::duration<double>(
                              std::chrono::steady_clock::now() - oldest)
                              .count();
    metrics_.observe("pbft_shard_handoff_seconds", waited);
    ++shard_handoffs_;
    shard_handoff_s_ += waited;
  }
  for (auto& k : in) {
    const uint64_t key = shard_link_key(k.shard, k.conn_id);
    if (k.kind == KInbound::kGatewayUp) {
      sharded_gateways_.insert(key);
      continue;
    }
    if (k.kind == KInbound::kGatewayDown) {
      if (sharded_gateways_.erase(key) > 0 && !stopping_) {
        ++gateway_failovers_;
        metrics_.inc("pbft_gateway_failovers_total");
        FlightRecorder& fl = global_flight();
        if (fl.enabled()) {
          fl.record(kFlightGatewayFailover, replica_->view(),
                    (int64_t)k.conn_id, -1);
        }
      }
      continue;
    }
    if (!k.msg) continue;
    ++frames_in_;
    if (auto* req = std::get_if<ClientRequest>(&*k.msg)) {
      if (k.from_gateway) {
        note_gateway_route(req->client, key);
        ++gateway_forwarded_;
      }
      if (!maybe_reject_overload(*req)) {
        trace_request_rx(*req);
        emit(in_protocol([&] { return replica_->receive(*k.msg); }));
      }
    } else if (k.pre_authenticated) {
      // The pipeline verified this frame's MAC lane (ISSUE 14): no
      // verify queue, straight dispatch.
      emit(in_protocol(
          [&] { return replica_->receive_authenticated(*k.msg); }));
    } else if (k.has_signable) {
      emit(in_protocol(
          [&] { return replica_->receive(*k.msg, k.signable); }));
    } else {
      emit(in_protocol([&] { return replica_->receive(*k.msg); }));
    }
  }
}

void ReplicaServer::aggregate_shard_metrics() {
  if (!shards_) return;
  shards_->set_clocks_on(loop_clock_.on);
  // The shards' wakeups and MAC frames go with this thread's own, in
  // fold_counters; so do the front-end threads' stage clocks.
  fold_delta(shards_->cross_thread_wakes(), &seen_cross_wakes_,
             "pbft_cross_thread_wakes_total");
  fold_delta(shards_->backpressure_events(), &seen_shard_backpressure_,
             "pbft_write_backpressure_events_total");
  fold_delta(shards_->chaos_dropped(), &seen_shard_chaos_,
             "pbft_chaos_dropped_total");
  fold_delta(shards_->broadcast_encodes(), &seen_shard_encodes_,
             "pbft_broadcast_encodes_total");
  fold_delta(shards_->pipeline_dropped() + shards_->inbox_dropped() +
                 shards_->replies_dropped(),
             &seen_shard_dropped_, "pbft_shard_dropped_total");
  metrics_.set_gauge("pbft_crypto_offload_queue_depth",
                     (double)shards_->crypto_queue_depth());
}

std::string ReplicaServer::peer_addr(int64_t dest) {
  const auto& ident = cfg_.replicas[dest];
  if (ident.port != 0) return ident.host + ":" + std::to_string(ident.port);
  auto d = discovered_addrs_.find(dest);  // mDNS-equivalent addressing
  return d == discovered_addrs_.end() ? std::string() : d->second;
}

void ReplicaServer::register_conn(Conn& c) {
  poller_->add(c.fd, (uint64_t)(uintptr_t)&c, /*edge=*/true);
  if (c.connecting || !c.out.empty()) {
    // Fallback backend: arm POLLOUT for connect completion / queued
    // bytes (no-op under epoll — EPOLLOUT is edge-armed at add).
    poller_->set_write_interest(c.fd, true);
  }
}

// The async verifier's fd lives only while a launch is in flight, so it
// registers per launch and deregisters at completion/wedge — LEVEL
// triggered: poll_result reads partially and must re-fire while verdict
// bytes remain buffered.
void ReplicaServer::register_verifier_fd() {
  int fd = verifier_->async_fd();
  if (fd < 0 || fd == verifier_fd_) return;
  poller_->add(fd, kTagVerifier, /*edge=*/false);
  verifier_fd_ = fd;
}

void ReplicaServer::unregister_verifier_fd() {
  if (verifier_fd_ < 0) return;
  poller_->remove(verifier_fd_);
  verifier_fd_ = -1;
}

void ReplicaServer::accept_ready() {
  LoopClock::Scope in(loop_clock_, kLoopRead);
  for (;;) {
    int fd = accept(listen_fd_, nullptr, nullptr);
    if (fd < 0) return;
    set_nonblocking(fd);
    tune_stream_socket(fd);
    auto c = std::make_unique<Conn>();
    c->fd = fd;
    c->rbuf.data = pool_.acquire();
    register_conn(*c);
    conns_.push_back(std::move(c));
  }
}

void ReplicaServer::handle_readable(Conn& c) {
  LoopClock::Scope in(loop_clock_, kLoopRead);
  // Drains to EAGAIN — REQUIRED under the edge-triggered backend: a
  // partial drain would leave buffered bytes with no further edge.
  char buf[65536];
  for (;;) {
    ssize_t r = read(c.fd, buf, sizeof(buf));
    if (r > 0) {
      c.rbuf.append(buf, (size_t)r);
      continue;
    }
    if (r < 0 && (errno == EAGAIN || errno == EWOULDBLOCK)) break;
    // EOF or error: a raw-JSON client may terminate its message by close.
    if (!c.rbuf.empty()) process_buffer(c);
    mark_closed(c);
    return;
  }
  process_buffer(c);
}

void ReplicaServer::process_buffer(Conn& c) {
  if (c.close_when_flushed) {
    // One-shot outbound reply: anything the dialed endpoint sends back is
    // discarded, never parsed — the address came from an UNTRUSTED client
    // request, and feeding its bytes into the replica would be an
    // unauthenticated request-injection channel. In the common path the
    // conn closes at flush before reading anything; this guard covers the
    // partial-flush window where the conn stays open and readable.
    c.rbuf.reset();
    return;
  }
  if (!c.sniffed && !c.rbuf.empty()) {
    c.sniffed = true;
    // The client gateway keeps the reference's telnet-able contract: raw
    // JSON (no length prefix), one message per line/connection.
    c.raw_json = c.rbuf.at(0) == '{';
  }
  if (c.raw_json) {
    for (;;) {
      auto nl = c.rbuf.find('\n');
      std::string payload;
      if (nl != std::string::npos) {
        payload = c.rbuf.take(nl);
        c.rbuf.consume(1);
      } else if (c.closed || c.fd < 0) {
        payload = c.rbuf.take(c.rbuf.size());
      } else {
        // Wait for more bytes — but try a complete object eagerly so a
        // no-newline sender (telnet paste) still goes through. Bounded:
        // a line larger than 1 MiB on this unauthenticated socket is a
        // protocol violation and drops the connection (the framed path
        // caps at 2^24 below; the raw path must not buffer without bound).
        if (Json::parse(c.rbuf.str())) {
          payload = c.rbuf.take(c.rbuf.size());
        } else if (c.rbuf.size() > (1u << 20)) {
          mark_closed(c);
          return;
        } else {
          return;
        }
      }
      while (!payload.empty() &&
             (payload.back() == '\r' || payload.back() == ' '))
        payload.pop_back();
      if (payload.empty()) {
        if (c.rbuf.empty()) return;
        continue;
      }
      auto msg = from_payload(payload);
      if (msg) {
        ++frames_in_;
        auto* req = std::get_if<ClientRequest>(&*msg);
        if (req == nullptr || !maybe_reject_overload(*req)) {
          if (req != nullptr) trace_request_rx(*req);
          emit(in_protocol([&] { return replica_->receive(*msg); }));
        }
      }
      if (c.rbuf.empty()) return;
    }
  }
  // Framed replica-to-replica stream.
  for (;;) {
    if (c.rbuf.size() < 4) return;
    uint32_t len = ((uint32_t)c.rbuf.at(0) << 24) |
                   ((uint32_t)c.rbuf.at(1) << 16) |
                   ((uint32_t)c.rbuf.at(2) << 8) | (uint32_t)c.rbuf.at(3);
    if (len > (1u << 24)) {  // corrupt frame; drop the connection
      mark_closed(c);
      return;
    }
    if (c.rbuf.size() < 4 + (size_t)len) return;
    c.rbuf.consume(4);
    std::string payload = c.rbuf.take(len);
    if (!handle_peer_frame(c, std::move(payload))) return;
  }
}

std::string frame_payload(const std::string& payload) {
  uint32_t n = (uint32_t)payload.size();
  std::string out;
  out.reserve(4 + payload.size());
  out.push_back((char)(n >> 24));
  out.push_back((char)(n >> 16));
  out.push_back((char)(n >> 8));
  out.push_back((char)n);
  out += payload;
  return out;
}

void ReplicaServer::count_backpressure() {
  ++backpressure_events_;
  metrics_.inc("pbft_write_backpressure_events_total");
}

bool ReplicaServer::outbound_has_room(Conn& c) {
  if (c.out.bytes <= kMaxConnOutbound) return true;
  // Drop-and-count (ISSUE 10 satellite): a slow or black-holed reader
  // must not grow this queue without limit — PBFT retransmission absorbs
  // the dropped frame exactly like a chaos link drop.
  count_backpressure();
  return false;
}

void ReplicaServer::queue_bytes(Conn& c, const std::string& framed) {
  auto& q = c.out;
  // Coalesce into pooled blocks so one send() carries many frames; the
  // back block may be the partially-sent front — appending to it is fine
  // (flush addresses data()+front_pos each call).
  if (!q.blocks.empty() && q.blocks.back().size() + framed.size() <= kMaxSendBlock) {
    q.blocks.back() += framed;
  } else {
    std::string b = pool_.acquire();
    b += framed;
    q.blocks.push_back(std::move(b));
  }
  q.bytes += framed.size();
  ++frames_out_;  // pbft_frames_out_total: folded at the scrape
}

bool ReplicaServer::reject_conn(Conn& c, const std::string& reason) {
  std::fprintf(stderr, "replica %lld: rejecting peer link: %s\n",
               (long long)id_, reason.c_str());
  queue_bytes(c, frame_payload(SecureChannel::reject_payload(reason)));
  flush(c);  // best-effort: the reject may be truncated if the link stalls
  if (!c.closed) {
    mark_closed(c);
  }
  return false;
}

bool ReplicaServer::fail_conn(Conn& c, const std::string& reason) {
  std::fprintf(stderr, "replica %lld: dropping peer link: %s\n",
               (long long)id_, reason.c_str());
  if (!c.closed) {
    mark_closed(c);
  }
  return false;
}

bool ReplicaServer::handle_peer_frame(Conn& c, std::string payload) {
  if (c.peer_dest >= 0) {
    // Dialed (initiator) link: only handshake replies and rejects arrive.
    if (c.chan && !c.chan->established()) {
      auto j = Json::parse(payload);
      if (!j) return fail_conn(c, "malformed handshake reply");
      if (c.chan->auth_only()) {
        // Authenticator mode on a plaintext cluster: a responder that
        // answered the mac-offering hello with a classic hello-ack
        // (pre-1.3.0 or signature-mode config) downgrades this link to
        // the plain flavor — its ack still carried the codec offer.
        const Json* t = j->find("type");
        if (t && t->is_string() && t->as_string() == "reject") {
          const Json* reason = j->find("reason");
          return fail_conn(c, "peer rejected link: " +
                                  (reason && reason->is_string()
                                       ? reason->as_string()
                                       : "<no reason>"));
        }
        const Json* eph = j->find("eph");
        if (!eph || !eph->is_string()) {
          c.chan.reset();
          if (t && t->is_string() && t->as_string() == "hello") {
            c.codec_binary = hello_offers_binary(*j);
          }
          for (auto& p : c.pending) queue_bytes(c, frame_payload(p));
          c.pending.clear();
          flush(c);
          return !c.closed;
        }
      }
      auto auth = c.chan->on_hello_reply(*j);
      if (!auth) return fail_conn(c, c.chan->error());
      // hello_r carries the responder's codec offer: binary-v2 from here
      // on when both sides speak it (sends queued pre-handshake were
      // already JSON-encoded; mixed frames on one link are fine — the
      // receiver detects the codec per frame). The mac offer rides the
      // same frame: a mutually-offered link registers its sender-side
      // lane key so broadcasts grow a lane for this peer.
      c.codec_binary = hello_offers_binary(*j);
      if (c.chan->mac_negotiated()) {
        c.mac_ready = true;
        std::array<uint8_t, 32> key;
        std::memcpy(key.data(), c.chan->auth_send_key(), 32);
        mac_send_keys_[c.peer_dest] = key;
      } else {
        mac_send_keys_.erase(c.peer_dest);
      }
      const bool auth_only = c.chan->auth_only();
      queue_bytes(c, frame_payload(*auth));
      for (auto& p : c.pending) {
        queue_bytes(
            c, frame_payload(auth_only ? p : c.chan->seal_frame(p)));
      }
      c.pending.clear();
      flush(c);
      return !c.closed;
    }
    if (!c.chan) {  // plaintext link: hello-ack (codec offer) or reject
      auto j = Json::parse(payload);
      const Json* t = j ? j->find("type") : nullptr;
      if (t && t->is_string() && t->as_string() == "reject") {
        const Json* r = j->find("reason");
        return fail_conn(c, "peer rejected link: " +
                                (r && r->is_string() ? r->as_string()
                                                     : "<no reason>"));
      }
      if (t && t->is_string() && t->as_string() == "hello") {
        c.codec_binary = hello_offers_binary(*j);
      }
      return true;
    }
    if (c.chan && !c.chan->auth_only()) {
      auto pt = c.chan->open_frame(payload);
      if (!pt) return fail_conn(c, c.chan->error());
      payload = std::move(*pt);
    }
  } else if (!c.hello_seen) {
    // Accepted link: the first frame carries the protocol version.
    auto j = Json::parse(payload);
    const Json* t = j ? j->find("type") : nullptr;
    bool is_hello = t && t->is_string() && t->as_string() == "hello";
    if (is_hello) {
      std::string err;
      if (!SecureChannel::check_version(*j, &err)) return reject_conn(c, err);
      c.hello_seen = true;
      c.peer_mac = fastpath_mac_ && hello_offers_mac(*j);
      // Gateway trust (ISSUE 10): a hello carrying role=gateway marks
      // this link as a client-gateway — framed client requests arrive on
      // it, and replies for those clients fan BACK over it instead of
      // per-reply dial-backs. Gateways hold no replica identity, so the
      // signed-DH handshake cannot admit them: plaintext clusters only.
      const Json* role = j->find("role");
      if (role && role->is_string() && role->as_string() == "gateway") {
        if (cfg_.secure) {
          return reject_conn(
              c, "gateway links require a plaintext cluster (a gateway "
                 "has no replica identity to authenticate)");
        }
        c.gateway = true;
        c.link_id = ++gateway_link_seq_;
        gateway_links_[c.link_id] = &c;
      }
      const Json* eph = j->find("eph");
      if (cfg_.secure) {
        c.chan = std::make_unique<SecureChannel>(&cfg_, id_, seed_,
                                                 /*initiator=*/false,
                                                 /*expected_peer=*/-1,
                                                 fastpath_mac_);
        auto reply = c.chan->on_hello(*j);
        if (!reply) return reject_conn(c, c.chan->error());
        queue_bytes(c, frame_payload(*reply));
        flush(c);
      } else if (c.peer_mac && eph && eph->is_string()) {
        // Authenticator mode on a plaintext cluster (ISSUE 14): the
        // SAME signed station-to-station handshake runs purely for
        // lane-key agreement + peer identity — frames after it stay
        // plaintext (auth-only channel, never sealed/opened).
        c.chan = std::make_unique<SecureChannel>(&cfg_, id_, seed_,
                                                 /*initiator=*/false,
                                                 /*expected_peer=*/-1,
                                                 fastpath_mac_,
                                                 /*auth_only=*/true);
        auto reply = c.chan->on_hello(*j);
        if (!reply) return reject_conn(c, c.chan->error());
        queue_bytes(c, frame_payload(*reply));
        flush(c);
      } else {
        // Plaintext hello-ack: advertise this node's version + codec
        // (and fast-path) offers so the dialing peer can negotiate
        // binary-v2 / mac (a 1.0.0 initiator parses and ignores any
        // non-reject frame).
        queue_bytes(c, frame_payload(
                           SecureChannel::plain_hello(id_, fastpath_mac_)));
        flush(c);
      }
      return !c.closed;
    }
    if (cfg_.secure) {
      return reject_conn(
          c, "plaintext peer rejected: first frame must be an "
             "encrypted-link hello");
    }
    c.hello_seen = true;  // tooling compat: framed protocol, no hello
  } else if (c.chan && !c.chan->established()) {
    auto j = Json::parse(payload);
    if (!j || !c.chan->on_auth(*j)) {
      return reject_conn(c, c.chan->error().empty() ? "malformed auth frame"
                                                    : c.chan->error());
    }
    // Established: an inbound mac-negotiated link verifies lanes with
    // the channel's recv key from here on.
    if (c.chan->mac_negotiated()) c.mac_ready = true;
    return true;
  } else if (c.chan && !c.chan->auth_only()) {
    auto pt = c.chan->open_frame(payload);
    if (!pt) return fail_conn(c, c.chan->error());
    payload = std::move(*pt);
  }
  auto msg = from_payload(payload);
  if (msg) {
    // Authenticator fast path (ISSUE 14): a MAC frame on a
    // mac-negotiated link verifies THIS replica's lane + the claimed
    // sender against the link's authenticated peer, then dispatches
    // WITHOUT the verify queue. No lane for us (link joined
    // mid-fan-out) falls through to the signature path the embedded
    // sig still serves; a lane MISMATCH drops and counts.
    if (c.mac_ready && c.chan && payload_is_mac_frame(payload)) {
      uint8_t lane[16];
      if (mac_frame_lane(payload, id_, lane)) {
        uint8_t signable[32], want[16];
        message_signable_from_payload(payload, *msg, signable);
        mac_tag(c.chan->auth_recv_key(), signable, want);
        if (!mac_tag_equal(lane, want) ||
            mac_claimed_replica(*msg) != c.chan->peer_id()) {
          ++mac_rejected_;
          return true;
        }
        ++frames_in_;
        emit(in_protocol(
            [&] { return replica_->receive_authenticated(*msg); }));
        return true;
      }
    }
    ++frames_in_;
    if (std::holds_alternative<ClientRequest>(*msg)) {
      const auto& req = std::get<ClientRequest>(*msg);
      if (c.gateway) {
        // Remember the forwarding link so this client's reply can fan
        // back over it (exact route; the "gw/" prefix fallback covers
        // replicas that only saw the request via pre-prepare). Noted
        // BEFORE admission so an overloaded line can route back too.
        note_gateway_route(req.client, c.link_id);
        ++gateway_forwarded_;
      }
      if (!maybe_reject_overload(req)) {
        trace_request_rx(req);
        emit(in_protocol([&] { return replica_->receive(*msg); }));
      }
    } else {
      // Receive-side canonical reuse: derive the signable digest from
      // the framed bytes we already hold (sig-splice for JSON, fixed
      // template for binary) so the verify queue never re-serializes.
      uint8_t signable[32];
      message_signable_from_payload(payload, *msg, signable);
      emit(in_protocol([&] { return replica_->receive(*msg, signable); }));
    }
  }
  return true;
}

void ReplicaServer::mark_closed(Conn& c) {
  if (c.closed) return;
  // A dialed mac link's lane key dies with the connection (the redial's
  // handshake derives fresh ones).
  if (c.peer_dest >= 0 && c.mac_ready) mac_send_keys_.erase(c.peer_dest);
  if (c.fd >= 0) {
    // Deregister BEFORE close: the fallback backend keeps polling a
    // removed fd otherwise (POLLNVAL forever); epoll auto-deregisters on
    // close, so the explicit remove is merely redundant there.
    poller_->remove(c.fd);
    close(c.fd);
  }
  c.closed = true;
  // Return pooled storage: the recv buffer and every queued send block
  // go back to the free list for the next accept/dial.
  pool_.release(std::move(c.rbuf.data));
  c.rbuf = RecvBuf{};
  for (auto& b : c.out.blocks) pool_.release(std::move(b));
  c.out = SendQueue{};
  if (c.gateway) {
    gateway_links_.erase(c.link_id);
    if (!stopping_) {
      // A live gateway link died (ISSUE 12): its clients must fail over
      // to another gateway — count it so a chaos arm can attribute the
      // blip.
      ++gateway_failovers_;
      metrics_.inc("pbft_gateway_failovers_total");
      FlightRecorder& fl = global_flight();
      if (fl.enabled()) {
        fl.record(kFlightGatewayFailover, replica_->view(),
                  (int64_t)c.link_id, -1);
      }
    }
  }
  if (c.close_when_flushed) {
    if (reply_dials_in_flight_ > 0) --reply_dials_in_flight_;
    if (!c.reply_addr.empty()) reply_addrs_in_flight_.erase(c.reply_addr);
  }
}

void ReplicaServer::finish_connect(Conn& c) {
  int err = 0;
  socklen_t len = sizeof(err);
  if (getsockopt(c.fd, SOL_SOCKET, SO_ERROR, &err, &len) != 0 || err != 0) {
    mark_closed(c);
    return;
  }
  c.connecting = false;
  flush(c);  // buffered hello / reply bytes go out now
}

void ReplicaServer::flush(Conn& c) {
  if (c.connecting) return;  // nothing sendable until the connect lands
  LoopClock::Scope in(loop_clock_, kLoopSend);  // from wherever it is called
  SendQueue& q = c.out;
  while (!q.blocks.empty()) {
    std::string& b = q.blocks.front();
    size_t avail = b.size() - q.front_pos;
    if (avail == 0) {  // fully-sent block: recycle and advance
      pool_.release(std::move(b));
      q.blocks.pop_front();
      q.front_pos = 0;
      continue;
    }
    ssize_t w = send(c.fd, b.data() + q.front_pos, avail, MSG_NOSIGNAL);
    ++send_calls_;  // pbft_send_calls_total: folded at the scrape
    if (w > 0) {
      q.front_pos += (size_t)w;
      q.bytes -= (size_t)w;
      continue;
    }
    if (w < 0 && (errno == EAGAIN || errno == EWOULDBLOCK)) {
      // Partial-write backpressure: the kernel buffer is full. Resume on
      // write readiness — an EPOLLOUT edge on the ET backend (armed once
      // at registration), explicit POLLOUT interest on the fallback. One
      // backpressure count per backed-up episode (the latch).
      poller_->set_write_interest(c.fd, true);
      if (!c.backpressured) {
        c.backpressured = true;
        count_backpressure();
      }
      return;
    }
    mark_closed(c);
    return;
  }
  q.front_pos = 0;
  c.backpressured = false;
  poller_->set_write_interest(c.fd, false);
  if (c.close_when_flushed) {  // one-shot dial-back reply delivered
    mark_closed(c);
  }
}

void ReplicaServer::flush_once_an_emit(Conn& c) {
  if (emit_depth_ == 0) {
    flush(c);
  } else if (!c.touched) {
    c.touched = true;
    touched_.push_back(&c);
  }
}

// One send() a connection for everything the emit queued on it (more only
// past a 64 KiB block or a full socket). A Conn lives until the end-of-
// pass sweep, so the pointers hold; one that closed since it was touched
// (its fd is gone, perhaps already another socket's) is passed over.
void ReplicaServer::flush_touched() {
  for (Conn* c : touched_) {  // flush() never adds one
    c->touched = false;
    if (!c->closed) flush(*c);
  }
  touched_.clear();
}

bool ReplicaServer::set_trace_file(const std::string& path) {
  if (trace_fp_) std::fclose(trace_fp_);
  trace_fp_ = std::fopen(path.c_str(), "a");
  if (!trace_fp_) {
    std::fprintf(stderr, "replica %lld: cannot open trace file %s\n",
                 (long long)id_, path.c_str());
    return false;
  }
  return true;
}

namespace {
double trace_now() {
  return std::chrono::duration<double>(
             std::chrono::steady_clock::now().time_since_epoch())
      .count();
}
}  // namespace

// Event schemas match the Python tracer's (pbft_tpu/net/server.py) so a
// mixed-runtime cluster's traces merge without per-runtime special cases.
// The line is written when the batch's verdicts have been worked through
// (its ts is the end of the apply). loop_us: the loop clock's seven
// running totals at that instant, in kLoopStageNames' order, so any two
// lines of a replica bracket an interval with its split by kind of work
// (scripts/trace_report.py sets them against verifyd's launch log: every
// stamp is CLOCK_MONOTONIC).
void ReplicaServer::trace_batch(int64_t size, int64_t rejected, double secs,
                                bool ahead, double apply_s) {
  if (!trace_fp_) return;
  const auto at = loop_clock_.on ? loop_clock_.sync()
                                 : std::chrono::steady_clock::now();
  const double now =
      std::chrono::duration<double>(at.time_since_epoch()).count();
  char apply[48] = "";
  if (apply_s >= 0) {
    std::snprintf(apply, sizeof(apply), ",\"apply_s\":%.6f", apply_s);
  }
  std::fprintf(trace_fp_,
               "{\"ts\":%.6f,\"ev\":\"verify_batch\",\"replica\":%lld,"
               "\"size\":%lld,\"rejected\":%lld,\"secs\":%.6f,\"view\":%lld,"
               "\"executed\":%lld,\"ahead\":%d%s,\"loop_us\":[",
               now, (long long)id_, (long long)size, (long long)rejected, secs,
               (long long)replica_->view(),
               (long long)replica_->executed_upto(), ahead ? 1 : 0, apply);
  for (int i = 0; i < kLoopStages; ++i) {
    std::fprintf(trace_fp_, i ? ",%lld" : "%lld",
                 (long long)(loop_clock_.ns[i] / 1000));
  }
  if (shards_) {
    // The front-end threads' running totals, summed over this replica's
    // shards and pipelines (kShardStageNames' / kPipeStageNames' order),
    // and the hand-offs observed so far with the seconds they took.
    for (const bool pipes : {false, true}) {
      std::fputs(pipes ? "],\"pipe_us\":[" : "],\"shard_us\":[", trace_fp_);
      for (int i = 0; i < kFrontStages; ++i) {
        std::fprintf(trace_fp_, i ? ",%lld" : "%lld",
                     (long long)shards_->front_stage_us(pipes, i));
      }
    }
    std::fprintf(trace_fp_, "],\"handoff\":[%lld,%.6f", (long long)shard_handoffs_,
                 shard_handoff_s_);
  }
  std::fputs("]}\n", trace_fp_);
  std::fflush(trace_fp_);
}

void ReplicaServer::trace_view_change(int backoff) {
  if (!trace_fp_) return;
  std::fprintf(trace_fp_,
               "{\"ts\":%.6f,\"ev\":\"view_change_start\",\"replica\":%lld,"
               "\"pending_view\":%lld,\"backoff\":%d}\n",
               trace_now(), (long long)id_, (long long)(replica_->view() + 1),
               backoff);
  std::fflush(trace_fp_);
}

namespace {
// Minimal JSON string escaping for trace fields carrying client input
// (the dial-back address): quote/backslash escaped, control bytes
// dropped. The Python tracer json-escapes implicitly; this keeps mixed
// traces parseable even against a hostile client string.
std::string json_escape(const std::string& s) {
  std::string out;
  out.reserve(s.size());
  for (char ch : s) {
    if (ch == '"' || ch == '\\') {
      out.push_back('\\');
      out.push_back(ch);
    } else if ((unsigned char)ch >= 0x20) {
      out.push_back(ch);
    }
  }
  return out;
}
}  // namespace

void ReplicaServer::trace_request_rx(const ClientRequest& req) {
  // The oldest request of the batch this one opens: the start of
  // pbft_request_wait_seconds. A request the replica then drops as a
  // duplicate leaves the batch empty and the next one stamps again.
  if ((metrics_.enabled || trace_fp_) && replica_->is_primary() &&
      replica_->open_batch_size() == 0) {
    batch_oldest_at_ = trace_now();
  }
  FlightRecorder& fl = global_flight();
  if (fl.enabled()) {
    fl.record(kFlightRequestRx, replica_->view(), req.timestamp, -1);
  }
  if (!trace_fp_) return;
  std::fprintf(trace_fp_,
               "{\"ts\":%.6f,\"ev\":\"request_rx\",\"replica\":%lld,"
               "\"client\":\"%s\",\"req_ts\":%lld}\n",
               trace_now(), (long long)id_,
               json_escape(req.client).c_str(), (long long)req.timestamp);
  std::fflush(trace_fp_);
}

void ReplicaServer::trace_batch_sealed(const PrePrepare& pp) {
  // Flight coverage comes from the "request" phase transition (the seal
  // itself); this emitter only owns the JSONL join record.
  if (!trace_fp_) return;
  const double wait_s = pending_batch_wait_s_;  // on_phase("request")
  pending_batch_wait_s_ = 0.0;
  std::string reqs;
  for (const auto& r : pp.requests) {
    if (!reqs.empty()) reqs += ",";
    reqs += "[\"" + json_escape(r.client) + "\"," +
            std::to_string(r.timestamp) + "]";
  }
  std::fprintf(trace_fp_,
               "{\"ts\":%.6f,\"ev\":\"batch_sealed\",\"replica\":%lld,"
               "\"view\":%lld,\"seq\":%lld,\"batch\":%lld,\"wait_s\":%.6f,"
               "\"reqs\":[%s]}\n",
               trace_now(), (long long)id_, (long long)pp.view,
               (long long)pp.seq, (long long)pp.requests.size(), wait_s,
               reqs.c_str());
  std::fflush(trace_fp_);
}

void ReplicaServer::trace_reply_tx(const ClientReply& reply) {
  FlightRecorder& fl = global_flight();
  if (fl.enabled()) {
    fl.record(kFlightReplyTx, reply.view, reply.timestamp, -1);
  }
  if (!trace_fp_) return;
  std::fprintf(trace_fp_,
               "{\"ts\":%.6f,\"ev\":\"reply_tx\",\"replica\":%lld,"
               "\"client\":\"%s\",\"req_ts\":%lld,\"view\":%lld}\n",
               trace_now(), (long long)id_,
               json_escape(reply.client).c_str(), (long long)reply.timestamp,
               (long long)reply.view);
  std::fflush(trace_fp_);
}

void ReplicaServer::on_view_event(const char* ev, int64_t v) {
  const bool sent = std::strcmp(ev, "view_change_sent") == 0;
  FlightRecorder& fl = global_flight();
  if (fl.enabled()) {
    fl.record(sent ? kFlightViewChangeSent : kFlightNewViewInstalled, v, 0,
              -1);
  }
  if (!trace_fp_) return;
  if (sent) {
    std::fprintf(trace_fp_,
                 "{\"ts\":%.6f,\"ev\":\"view_change_sent\",\"replica\":%lld,"
                 "\"pending_view\":%lld}\n",
                 trace_now(), (long long)id_, (long long)v);
  } else {
    std::fprintf(trace_fp_,
                 "{\"ts\":%.6f,\"ev\":\"new_view_installed\",\"replica\":"
                 "%lld,\"view\":%lld}\n",
                 trace_now(), (long long)id_, (long long)v);
  }
  std::fflush(trace_fp_);
}

// Consensus-phase spans (Replica::phase_hook target). Stamp indices:
// 0=request (primary only), 1=pre_prepare, 2=prepared, 3=committed;
// "executed" closes the span. Schemas/metric names are the cross-runtime
// contract (pbft_tpu/utils/trace_schema.py) — the Python runtime's
// ConsensusSpans must stay field-for-field identical.
void ReplicaServer::on_phase(const char* phase, int64_t view, int64_t seq) {
  FlightRecorder& fl = global_flight();
  if (fl.enabled()) {
    // The "request" transition is the primary's seal — recorded under the
    // batch_sealed flight id (trace_schema FLIGHT_EVENTS contract).
    uint16_t ev = !std::strcmp(phase, "request")       ? kFlightBatchSealed
                  : !std::strcmp(phase, "pre_prepare") ? kFlightPrePrepare
                  : !std::strcmp(phase, "prepared")    ? kFlightPrepared
                  : !std::strcmp(phase, "committed")   ? kFlightCommitted
                                                       : kFlightExecuted;
    fl.record(ev, view, seq, -1);
  }
  if (!metrics_.enabled && !trace_fp_) return;
  static constexpr size_t kMaxOpenSpans = 4096;
  const double now = trace_now();
  const std::pair<int64_t, int64_t> key{view, seq};
  auto it = open_spans_.find(key);
  if (std::strcmp(phase, "executed") != 0) {
    if (it == open_spans_.end()) {
      if (open_spans_.size() >= kMaxOpenSpans) {
        open_spans_.erase(open_spans_.begin());  // abandoned slot
      }
      it = open_spans_
               .emplace(key, std::array<double, 4>{NAN, NAN, NAN, NAN})
               .first;
    }
    int idx = !std::strcmp(phase, "request")       ? 0
              : !std::strcmp(phase, "pre_prepare") ? 1
              : !std::strcmp(phase, "prepared")    ? 2
                                                   : 3;
    if (std::isnan(it->second[idx])) it->second[idx] = now;
    if (idx == 0) {
      // The seal: the oldest request's wait at the primary, once a batch.
      pending_batch_wait_s_ =
          std::isnan(batch_oldest_at_) ? 0.0
                                       : std::max(0.0, now - batch_oldest_at_);
      batch_oldest_at_ = std::nan("");
      metrics_.observe("pbft_request_wait_seconds", pending_batch_wait_s_);
    }
    return;
  }
  if (cfg_.tentative) tentative_exec_at_[seq] = now;
  if (it == open_spans_.end()) return;  // evicted or never opened
  const std::array<double, 4> s = it->second;
  open_spans_.erase(it);
  metrics_.inc("pbft_executed_total");
  auto obs = [&](const char* name, double a, double b) {
    if (!std::isnan(a) && !std::isnan(b)) {
      metrics_.observe(name, std::max(0.0, b - a));
    }
  };
  obs("pbft_phase_pre_prepare_seconds", s[0], s[1]);
  obs("pbft_phase_prepare_seconds", s[1], s[2]);
  obs("pbft_phase_commit_seconds", s[2], s[3]);
  obs("pbft_phase_reply_seconds", s[3], now);
  const double start = !std::isnan(s[0]) ? s[0] : s[1];
  if (!std::isnan(start)) {
    metrics_.observe("pbft_request_reply_seconds", std::max(0.0, now - start));
  }
  if (!trace_fp_) return;
  char buf[512];
  int off = std::snprintf(
      buf, sizeof(buf),
      "{\"ts\":%.6f,\"ev\":\"consensus_span\",\"replica\":%lld,"
      "\"view\":%lld,\"seq\":%lld",
      now, (long long)id_, (long long)view, (long long)seq);
  const char* names[] = {"request", "pre_prepare", "prepared", "committed"};
  for (int i = 0; i < 4; ++i) {
    if (!std::isnan(s[i]) && off < (int)sizeof(buf)) {
      off += std::snprintf(buf + off, sizeof(buf) - off, ",\"%s\":%.6f",
                           names[i], s[i]);
    }
  }
  if (off < (int)sizeof(buf)) {
    off += std::snprintf(buf + off, sizeof(buf) - off, ",\"executed\":%.6f}",
                         now);
  }
  std::fprintf(trace_fp_, "%s\n", buf);
  std::fflush(trace_fp_);
}

void ReplicaServer::on_commit_floor(int64_t seq) {
  auto it = tentative_exec_at_.find(seq);
  if (it == tentative_exec_at_.end()) return;
  const double now = trace_now();
  const double lag_s = std::max(0.0, now - it->second);
  tentative_exec_at_.erase(tentative_exec_at_.begin(), std::next(it));
  metrics_.observe("pbft_tentative_commit_lag_seconds", lag_s);
  if (!trace_fp_) return;
  std::fprintf(trace_fp_,
               "{\"ts\":%.6f,\"ev\":\"commit_lag\",\"replica\":%lld,"
               "\"seq\":%lld,\"lag_s\":%.6f}\n",
               now, (long long)id_, (long long)seq, lag_s);
  std::fflush(trace_fp_);
}

std::string ReplicaServer::metrics_prometheus() {
  refresh_health();
  return metrics_.render_prometheus(std::to_string(id_));
}

void ReplicaServer::serve_metrics_ready() {
  for (;;) {
    int fd = accept(metrics_listen_fd_, nullptr, nullptr);
    if (fd < 0) return;
    tune_stream_socket(fd);
    // One-shot scrape, routed on the request line: "/status" gets the
    // health document (metrics_json) as JSON, anything else the full
    // Prometheus exposition. The request bytes may trail the accept, so
    // wait briefly (bounded — a poller pass must not hang on a client
    // that connects and says nothing); an empty read scrapes Prometheus.
    char sink[1024];
    struct timeval rcv_to{0, 250000};
    setsockopt(fd, SOL_SOCKET, SO_RCVTIMEO, &rcv_to, sizeof(rcv_to));
    ssize_t got = recv(fd, sink, sizeof(sink) - 1, 0);
    bool want_status = false;
    if (got > 0) {
      sink[got] = '\0';
      want_status = std::strstr(sink, " /status") != nullptr;
    }
    std::string body;  // either rendering refreshes health + folds first
    const char* content_type;
    if (want_status) {
      body = metrics_json();
      content_type = "application/json";
    } else {
      body = metrics_prometheus();
      content_type = "text/plain; version=0.0.4";
    }
    char hdr[160];
    int hn = std::snprintf(hdr, sizeof(hdr),
                           "HTTP/1.0 200 OK\r\n"
                           "Content-Type: %s\r\n"
                           "Content-Length: %zu\r\n\r\n",
                           content_type, body.size());
    std::string resp(hdr, (size_t)hn);
    resp += body;
    (void)send(fd, resp.data(), resp.size(), MSG_NOSIGNAL);
    (void)recv(fd, sink, sizeof(sink), MSG_DONTWAIT);  // avoid RST on close
    close(fd);
  }
}

void ReplicaServer::check_verify_deadline(
    std::chrono::steady_clock::time_point now) {
  if (!verify_inflight_) return;
  LoopClock::Scope in(loop_clock_, kLoopVerify);
  const double age =
      std::chrono::duration<double>(now - inflight_start_).count();
  metrics_.set_gauge("pbft_verify_inflight_age_seconds", age);
  if (verify_deadline_ms_ <= 0 ||
      now - inflight_start_ < std::chrono::milliseconds(verify_deadline_ms_)) {
    return;
  }
  // Wedged async verifier: the connection is
  // alive but the reply never comes, so verify_inflight_ would stay true
  // forever. Drop the transport and run the CPU safety net on the batch —
  // same degradation contract as a detected transport failure. Any late
  // reply lands on a closed socket; it cannot double-deliver.
  unregister_verifier_fd();  // before cancel closes the fd
  verifier_->cancel_inflight();
  ++verify_deadline_fired_;
  metrics_.inc("pbft_verify_deadline_fired_total");
  if (trace_fp_) {
    std::fprintf(trace_fp_,
                 "{\"ts\":%.6f,\"ev\":\"verify_deadline_fired\","
                 "\"replica\":%lld,\"size\":%lld,\"age_secs\":%.6f}\n",
                 trace_now(), (long long)id_,
                 (long long)inflight_items_.size(), age);
    std::fflush(trace_fp_);
  }
  CpuVerifier safety_net;
  auto verdicts = safety_net.verify_batch(inflight_items_);
  ++safety_net_batches_;
  keep_verdicts(std::move(verdicts));
}

void ReplicaServer::check_batch_flush(
    std::chrono::steady_clock::time_point now) {
  if (replica_->open_batch_size() == 0) {
    batch_window_open_ = false;
    return;
  }
  if (!batch_window_open_) {
    batch_window_open_ = true;
    batch_window_start_ = now;
  }
  if (cfg_.batch_flush_us > 0 &&
      now - batch_window_start_ <
          std::chrono::microseconds(cfg_.batch_flush_us)) {
    return;  // keep accumulating: more client requests may arrive
  }
  batch_window_open_ = false;
  emit(in_protocol([&] { return replica_->flush_open_batch(); }));
  // A seal refused by a closed watermark window leaves the batch open;
  // re-arm so the next tick retries instead of spinning the deadline
  // (the request wait runs on: batch_oldest_at_ is not touched).
  if (replica_->open_batch_size() > 0) {
    batch_window_open_ = true;
    batch_window_start_ = now;
  }
}

void ReplicaServer::run_verify_batch() {
  if (verify_inflight_) return;  // accumulate; finish_verify_async keeps
  launch_verify_span();          // ahead of the verdicts this pass kept
  if (kept_) {
    apply_kept_verdicts();
    // What that delivery queued for the replica itself has nothing on the
    // wire in front of it unless a span went ahead: launch it now.
    if (!verify_inflight_) launch_verify_span();
  }
}

void ReplicaServer::launch_verify_span() {
  size_t pending = replica_->unlaunched_count();
  metrics_.set_gauge("pbft_verify_queue_depth", (double)pending);
  if (pending == 0) {
    verify_window_open_ = false;
    inbox_waiting_ = false;
    return;
  }
  // From here the pass works on the verify inbox (a pass that finds it
  // empty, as every pass in MAC mode does, charges `verify` nothing).
  LoopClock::Scope in(loop_clock_, kLoopVerify);
  if (cfg_.verify_flush_us > 0) {
    // Bounded accumulation: hold the queue until the item target or the
    // deadline so one verifier launch carries a whole window instead of
    // one event-loop pass's trickle (network.json verify_flush_us/_items).
    // The target is sized to the backend's parallel capacity: a
    // pool-backed CpuVerifier with N lanes wants N windows per dispatch,
    // not the one-inflight-window shape the async remote path uses.
    int64_t target =
        cfg_.verify_flush_items > 0 ? cfg_.verify_flush_items : cfg_.batch_pad;
    target *= (int64_t)std::max<size_t>(1, verifier_->parallel_capacity());
    auto now = std::chrono::steady_clock::now();
    if (!verify_window_open_) {
      // Items that queued DURING the trip whose verdicts are kept have
      // already waited up to that round-trip: backdate the window to its
      // dispatch so the accumulation hold and the launch overlap instead
      // of serializing (an item's extra hold stays <= max(flush_us, RTT)).
      verify_window_open_ = true;
      verify_window_start_ = kept_ ? kept_->dispatched_at : now;
    }
    if ((int64_t)pending < target &&
        now - verify_window_start_ <
            std::chrono::microseconds(cfg_.verify_flush_us)) {
      return;
    }
    verify_window_open_ = false;
  }
  auto items = replica_->pending_items();
  if (items.empty()) {
    // Only pre-authenticated entries (MAC mode): they await no verdict
    // and drain behind the verdicts in front of them.
    inbox_waiting_ = false;
    return;
  }
  if (inbox_waiting_) {
    // How long the oldest item of this batch sat in the inbox: a message
    // that arrives while a batch is in flight waits out that whole trip.
    inbox_waiting_ = false;
    metrics_.observe("pbft_verify_inbox_wait_seconds",
                     std::chrono::duration<double>(
                         std::chrono::steady_clock::now() - inbox_since_)
                         .count());
  }
  // Async first (RemoteVerifier): ship the batch and keep the loop
  // draining sockets — the round-trip is where the next window's
  // occupancy accumulates. Falls through to the blocking path when the
  // backend is sync-only (CPU), the batch exceeds the async write
  // budget, or the transport is down.
  if (verifier_->begin_batch(items)) {
    verify_inflight_ = true;
    inflight_items_ = std::move(items);
    inflight_start_ = std::chrono::steady_clock::now();
    register_verifier_fd();
    if (kept_) {
      kept_->launched_ahead = true;
      ++launched_ahead_;
      metrics_.inc("pbft_verify_launched_ahead_total");
    }
    return;
  }
  apply_kept_verdicts();  // in inbox order: never behind a blocking verify
  auto t0 = std::chrono::steady_clock::now();
  auto verdicts = verifier_->verify_batch(items);
  double secs =
      std::chrono::duration<double>(std::chrono::steady_clock::now() - t0)
          .count();
  deliver_verified(secs, /*ahead=*/false, std::move(verdicts),
                   /*began=*/nullptr);
}

int64_t ReplicaServer::verify_service_fallbacks() const {
  return safety_net_batches_ + verifier_->host_fallbacks();
}

void ReplicaServer::deliver_verified(
    double secs, bool ahead, std::vector<uint8_t> verdicts,
    const std::chrono::steady_clock::time_point* began) {
  // The batch's own accounting is the verify inbox's; what the verdicts
  // set off below switches to protocol, wal and send.
  LoopClock::Scope in(loop_clock_, kLoopVerify);
  const size_t n_items = verdicts.size();
  int64_t rejected = 0;
  ++batches_run_;
  // Every host-fallback path ends here, so the counter metric follows
  // the total without a hook in each of them.
  if (int64_t fb = verify_service_fallbacks(); fb > fallbacks_reported_) {
    metrics_.inc("pbft_verify_service_fallbacks_total",
                 fb - fallbacks_reported_);
    fallbacks_reported_ = fb;
  }
  {
    FlightRecorder& fl = global_flight();
    if (fl.enabled()) {
      int64_t rej = 0;
      for (uint8_t v : verdicts) rej += v ? 0 : 1;
      fl.record(kFlightVerifyBatch, replica_->view(), (int64_t)n_items, rej);
    }
  }
  if (metrics_.enabled || trace_fp_) {  // batch boundaries only
    for (uint8_t v : verdicts) rejected += v ? 0 : 1;
    metrics_.inc("pbft_verify_batches_total");
    metrics_.inc("pbft_verify_items_total", (int64_t)n_items);
    metrics_.inc("pbft_verify_rejected_total", rejected);
    metrics_.observe("pbft_verify_batch_size", (double)n_items);
    metrics_.observe("pbft_verify_seconds", secs);
    metrics_.set_gauge("pbft_verify_inflight_age_seconds", secs);
    // Native verify-pool surface: exported whenever the pool has run
    // (CpuVerifier backend, or the CPU safety net behind a remote one).
    if (global_verify_pool_created()) {
      const VerifyPoolStats ps = global_verify_pool().stats();
      metrics_.set_gauge("pbft_verify_pool_threads", (double)ps.threads);
      metrics_.set_gauge("pbft_verify_pool_queue_depth",
                         (double)ps.last_queue_depth);
      metrics_.set_gauge("pbft_verify_pool_utilization", ps.utilization());
      if (ps.last_window_items > 0) {
        metrics_.observe("pbft_verify_pool_window_size",
                         (double)ps.last_window_items);
      }
    }
  }
  emit(in_protocol([&] { return replica_->deliver_verdicts(verdicts); }));
  if (!metrics_.enabled && !trace_fp_) return;
  double apply_s = -1.0;
  if (began) {
    // Dispatch, execute, sign, WAL flush, sends for ONE batch's verdicts:
    // the piece of the verify cycle that is the replica's own (one more
    // clock read a batch).
    apply_s = std::chrono::duration<double>(
                  std::chrono::steady_clock::now() - *began)
                  .count();
    ++verdict_applies_;
    verdict_apply_s_ += apply_s;
    metrics_.observe("pbft_verdict_apply_seconds", apply_s);
  }
  trace_batch((int64_t)n_items, rejected, secs, ahead, apply_s);
}

void ReplicaServer::finish_verify_async() {
  LoopClock::Scope in(loop_clock_, kLoopVerify);
  std::vector<uint8_t> verdicts;
  bool failed = false;
  if (!verifier_->poll_result(&verdicts, &failed)) return;  // partial read
  unregister_verifier_fd();
  if (failed) {
    // Service died mid-launch: a verifier outage degrades throughput,
    // never safety/liveness — re-verify this batch (the span that was on
    // the wire, nothing behind it) in-process.
    CpuVerifier safety_net;
    verdicts = safety_net.verify_batch(inflight_items_);
    ++safety_net_batches_;
  }
  keep_verdicts(std::move(verdicts));
}

void ReplicaServer::keep_verdicts(std::vector<uint8_t> verdicts) {
  apply_kept_verdicts();
  kept_ = KeptVerdicts{inflight_start_, std::chrono::steady_clock::now(),
                       /*launched_ahead=*/false, std::move(verdicts)};
  verify_inflight_ = false;
  inflight_items_.clear();
}

void ReplicaServer::apply_kept_verdicts() {
  if (!kept_) return;
  KeptVerdicts k = std::move(*kept_);
  kept_.reset();
  // One clock read ends pbft_verdict_held_seconds and begins
  // pbft_verdict_apply_seconds.
  const bool timed = metrics_.enabled || trace_fp_;
  std::chrono::steady_clock::time_point began{};
  if (timed) {
    began = std::chrono::steady_clock::now();
    // What keeping costs a batch: verdicts read -> their delivery begins
    // (the rest of the pass's events, the batch flush, the launch ahead).
    metrics_.observe("pbft_verdict_held_seconds",
                     std::chrono::duration<double>(began - k.read_at).count());
  }
  deliver_verified(
      std::chrono::duration<double>(k.read_at - k.dispatched_at).count(),
      k.launched_ahead, std::move(k.verdicts), timed ? &began : nullptr);
}

namespace {
template <class T, class = void>
struct has_sig : std::false_type {};
template <class T>
struct has_sig<T, std::void_t<decltype(std::declval<T&>().sig)>>
    : std::true_type {};

// The Byzantine signer's outgoing message: same content, garbage
// signature (mirrors the simulation mutator in bench/harness.py).
Message corrupt_sig(Message m) {
  std::visit(
      [](auto& v) {
        if constexpr (has_sig<std::decay_t<decltype(v)>>::value) {
          if (!v.sig.empty()) v.sig.assign(v.sig.size(), 'f');
        }
      },
      m);
  return m;
}
}  // namespace

void ReplicaServer::count_fault() {
  ++faults_injected_;
  metrics_.inc("pbft_faults_injected_total");
}

Message ReplicaServer::equivocate_variant(const PrePrepare& pp) {
  PrePrepare b = pp;
  for (auto& r : b.requests) r.operation += "#equiv";
  b.digest = b.batch_digest();
  uint8_t digest[32], sig[64];
  Message m(b);
  message_signable(m, digest);
  ed25519_sign(sig, seed_, digest, 32);
  std::get<PrePrepare>(m).sig = to_hex(sig, 64);
  return m;
}

// Serialize-once fan-out on whichever front end is active. Single loop:
// ONE canonical encode (and at most one binary-v2 encode, when any link
// negotiated it) per broadcast via EncodedOut — the per-peer loop is pick
// codec, seal (secure links), memcpy, flush. Multi-core: one ShardEncoded
// shared by every pipeline, whose lazy encodes run OFF this thread and
// still happen at most once per codec (its internal mutex), tallied into
// the shards' encode counter and folded into the metric by
// aggregate_shard_metrics.
void ReplicaServer::broadcast_message(const Message& m) {
  if (shards_) {
    auto enc = std::make_shared<ShardEncoded>(m, &shards_->encodes_total);
    for (int64_t dest = 0; dest < cfg_.n(); ++dest) {
      if (dest == id_) continue;
      std::string addr = peer_addr(dest);
      if (!addr.empty()) shards_->send_peer(dest, addr, enc);
    }
    ++broadcasts_;
    return;
  }
  EncodedOut enc(&m);
  for (int64_t dest = 0; dest < cfg_.n(); ++dest) {
    if (dest != id_) send_encoded(dest, enc);
  }
  ++broadcasts_;
  broadcast_encodes_ += enc.encodes;
  metrics_.inc("pbft_broadcast_encodes_total", enc.encodes);
}

bool ReplicaServer::enable_wal(const std::string& dir) {
  // Best-effort mkdir -p (one level): the launcher usually created it.
  ::mkdir(dir.c_str(), 0755);
  const std::string path =
      dir + "/replica-" + std::to_string(id_) + ".wal";
  wal_ = std::make_unique<Wal>();
  if (!wal_->open(path, cfg_.wal_fsync)) {
    std::fprintf(stderr,
                 "replica %lld: WAL open failed at %s (corrupt or "
                 "unwritable)\n",
                 (long long)id_, path.c_str());
    wal_.reset();
    return false;
  }
  wal_path_ = path;  // stat target for pbft_wal_disk_bytes
  replica_->set_wal(wal_.get());
  const WalState& rec = wal_->recovered();
  if (!rec.empty()) {
    const auto t0 = std::chrono::steady_clock::now();
    FlightRecorder& fl = global_flight();
    if (fl.enabled()) {
      fl.record(kFlightRecoveryStarted, rec.view,
                rec.has_checkpoint ? rec.checkpoint_seq : 0, -1);
    }
    replica_->restore_from_wal(rec);
    recovered_from_wal_ = true;
    recovery_seconds_ =
        std::chrono::duration<double>(std::chrono::steady_clock::now() - t0)
            .count();
    metrics_.set_gauge("pbft_recovery_seconds", recovery_seconds_);
    if (fl.enabled()) {
      fl.record(kFlightRecoveryComplete, replica_->view(),
                replica_->executed_upto(), -1);
    }
    std::fprintf(stderr,
                 "replica %lld: recovered from WAL (view=%lld, "
                 "executed_upto=%lld, %zu persisted votes)\n",
                 (long long)id_, (long long)replica_->view(),
                 (long long)replica_->executed_upto(), rec.votes.size());
  }
  return true;
}

void ReplicaServer::flush_wal() {
  // A call that finds nothing pending costs this compare and no clock
  // read; it stays with the stage that made it.
  if (!wal_ || wal_->pending() == 0) return;
  LoopClock::Scope in(loop_clock_, kLoopWal);
  const auto t0 = std::chrono::steady_clock::now();
  wal_->flush();
  metrics_.observe(
      "pbft_wal_flush_seconds",
      std::chrono::duration<double>(std::chrono::steady_clock::now() - t0)
          .count());
  const int64_t appends = wal_->appends();
  const int64_t fsyncs = wal_->fsyncs();
  const int64_t bytes = wal_->bytes_written();
  if (metrics_.enabled) {
    metrics_.inc("pbft_wal_appends_total", appends - seen_wal_appends_);
    metrics_.inc("pbft_wal_fsyncs_total", fsyncs - seen_wal_fsyncs_);
    metrics_.inc("pbft_wal_bytes_total", bytes - seen_wal_bytes_);
  }
  seen_wal_appends_ = appends;
  seen_wal_fsyncs_ = fsyncs;
  seen_wal_bytes_ = bytes;
}

void ReplicaServer::emit(Actions&& actions) {
  // Durability BEFORE visibility (ISSUE 15): every vote noted while the
  // replica produced these actions must hit stable storage before any
  // of them reaches a socket — one group-commit flush covers the whole
  // pass (a verify batch's worth of votes), keeping fsync off the
  // per-message path.
  if (wal_) flush_wal();
  // Everything after the flush is the sending side: encode, MAC tags,
  // queue, send(), the reply's way back. (What a self-delivered message
  // sets off switches to protocol again, nested.)
  LoopClock::Scope in(loop_clock_, kLoopSend);
  // A connection is flushed once for all that this emit queues on it, not
  // once a frame (ISSUE 41): send_encoded and send_gateway_reply put it on
  // touched_ while emit_depth_ > 0, the OUTERMOST emit flushes the list
  // where the votes are queued and again where the replies are. One frame
  // in an emit is one send(), as before.
  ++emit_depth_;
  // Verify-inbox wait: every receive() comes back through here, so the
  // first pass that finds an item no launch has taken stamps its arrival
  // — one clock read per wait, none per message.
  if (metrics_.enabled && !inbox_waiting_ &&
      replica_->unlaunched_count() > 0) {
    inbox_waiting_ = true;
    inbox_since_ = std::chrono::steady_clock::now();
  }
  const bool mute = fault_mode_ == FaultMode::kMute;
  for (auto& b : actions.broadcasts) {
    // A broadcast of our OWN pre-prepare is the seal of a request batch
    // (ISSUE 9 waterfall join record) — observed before the fault modes,
    // because even a mute/equivocating primary sealed locally.
    if (trace_fp_) {
      if (auto* pp = std::get_if<PrePrepare>(&b.msg)) {
        if (pp->replica == id_) trace_batch_sealed(*pp);
      }
    }
    if (mute) {  // receives but never sends (--fault mute)
      count_fault();
      continue;
    }
    if (fault_mode_ == FaultMode::kEquivocate) {
      // The equivocating primary's own pre-prepare forks: even-numbered
      // peers get the genuine batch, odd-numbered peers a conflicting
      // one — SAME (view, seq), different digest, both validly signed.
      // Neither side can reach a 2f+1 commit quorum at <= f faulty, the
      // round stalls, and the honest replicas' timers vote us out.
      auto* pp = std::get_if<PrePrepare>(&b.msg);
      if (pp && pp->replica == id_ && !pp->requests.empty()) {
        Message variant = equivocate_variant(*pp);
        if (shards_) {
          auto enc_a =
              std::make_shared<ShardEncoded>(b.msg, &shards_->encodes_total);
          auto enc_b =
              std::make_shared<ShardEncoded>(variant, &shards_->encodes_total);
          for (int64_t dest = 0; dest < cfg_.n(); ++dest) {
            if (dest == id_) continue;
            std::string addr = peer_addr(dest);
            if (!addr.empty()) {
              shards_->send_peer(dest, addr, dest % 2 == 0 ? enc_a : enc_b);
            }
          }
        } else {
          EncodedOut enc_a(&b.msg);
          EncodedOut enc_b(&variant);
          for (int64_t dest = 0; dest < cfg_.n(); ++dest) {
            if (dest != id_) {
              send_encoded(dest, dest % 2 == 0 ? enc_a : enc_b);
            }
          }
          broadcast_encodes_ += enc_a.encodes + enc_b.encodes;
          metrics_.inc("pbft_broadcast_encodes_total",
                       enc_a.encodes + enc_b.encodes);
        }
        count_fault();
        ++broadcasts_;
        continue;
      }
    }
    // The Byzantine corruption is applied once: every peer sees the same
    // garbage signature.
    Message corrupted;
    const Message* mp = &b.msg;
    if (fault_mode_ == FaultMode::kSigCorrupt) {
      corrupted = corrupt_sig(b.msg);
      mp = &corrupted;
      count_fault();
    }
    broadcast_message(*mp);
    if (fault_mode_ == FaultMode::kStutter) {
      // Seeded stale replays: rebroadcast an old (validly signed)
      // message alongside the fresh one. Honest replicas must treat the
      // replay as the duplicate it is.
      if (!stutter_history_.empty() &&
          std::uniform_real_distribution<double>()(chaos_rng_) < 0.3) {
        size_t pick = (size_t)(std::uniform_real_distribution<double>()(
                                   chaos_rng_) *
                               stutter_history_.size());
        if (pick >= stutter_history_.size()) pick = 0;
        broadcast_message(stutter_history_[pick]);
        count_fault();
      }
      stutter_history_.push_back(b.msg);
      if (stutter_history_.size() > 32) stutter_history_.pop_front();
    }
  }
  for (auto& s : actions.sends) {
    // A ClientRequest forwarded to the primary starts this replica's
    // request timer (PBFT §4.4: a backup waits for the request to
    // execute, else it suspects the primary).
    if (auto* req = std::get_if<ClientRequest>(&s.msg)) {
      if (vc_timeout_ms_ > 0 && waiting_requests_.size() < 10000) {
        waiting_requests_[{req->client, req->timestamp}] =
            std::chrono::steady_clock::now() +
            std::chrono::milliseconds(vc_timeout_ms_);
      }
    }
    send_to(s.dest, s.msg);
  }
  // The votes leave before the replies' JSON is built.
  if (emit_depth_ == 1) flush_touched();
  for (auto& r : actions.replies) {
    waiting_requests_.erase({r.msg.client, r.msg.timestamp});
    if (mute) {  // a mute replica never dials the client back either
      count_fault();
      continue;
    }
    trace_reply_tx(r.msg);
    if (r.msg.tentative) {
      // Fast-path coverage (ISSUE 14): the reply left at PREPARED, one
      // commit round-trip early.
      FlightRecorder& fl = global_flight();
      if (fl.enabled()) {
        fl.record(kFlightTentativeReply, r.msg.view, r.msg.timestamp, -1);
      }
    }
    dial_reply(r.client, r.msg);
  }
  if (--emit_depth_ == 0) flush_touched();
  observe_execution_metrics();
}

void ReplicaServer::observe_execution_metrics() {
  // Rollbacks ship to the black box whether or not metrics are on — a
  // rollback is a rare, load-bearing event (ISSUE 14).
  const int64_t t_roll = replica_->counters["tentative_rollbacks"];
  if (t_roll > seen_rollbacks_) {
    FlightRecorder& fl = global_flight();
    if (fl.enabled()) {
      fl.record(kFlightTentativeRollback, replica_->view(),
                t_roll - seen_rollbacks_, -1);
    }
    metrics_.inc("pbft_tentative_rollbacks_total", t_roll - seen_rollbacks_);
    seen_rollbacks_ = t_roll;
  }
  if (!metrics_.enabled) return;
  const int64_t t_exec = replica_->counters["tentative_executions"];
  if (t_exec > seen_tentative_) {
    metrics_.inc("pbft_tentative_executions_total", t_exec - seen_tentative_);
    seen_tentative_ = t_exec;
  }
  const int64_t refused = replica_->counters["seals_refused"];
  if (refused > seen_seals_refused_) {
    metrics_.inc("pbft_seal_refused_total", refused - seen_seals_refused_);
    seen_seals_refused_ = refused;
  }
  const int64_t inline_v = replica_->counters["inline_verifies"];
  if (inline_v > seen_inline_verifies_) {
    metrics_.inc("pbft_inline_verifies_total",
                 inline_v - seen_inline_verifies_);
    seen_inline_verifies_ = inline_v;
  }
  if (const int64_t signs = replica_->signs(); signs > seen_signs_) {
    metrics_.inc("pbft_signs_total", signs - seen_signs_);
    seen_signs_ = signs;
  }
  // Deltas of the replica's own counters: "executed" counts per REQUEST,
  // "rounds_executed" per sequence number — the two together are the
  // batching amplification factor (requests per three-phase instance).
  const int64_t executed = replica_->counters["executed"];
  const int64_t rounds = replica_->counters["rounds_executed"];
  if (executed > seen_executed_) {
    metrics_.inc("pbft_requests_executed_total", executed - seen_executed_);
    seen_executed_ = executed;
  }
  if (rounds > seen_rounds_) {
    metrics_.inc("pbft_consensus_rounds_total", rounds - seen_rounds_);
    seen_rounds_ = rounds;
  }
}

void ReplicaServer::check_progress_timer() {
  if (vc_timeout_ms_ <= 0) return;
  // The protocol's timers: what they find in Replica and what they set off.
  LoopClock::Scope in(loop_clock_, kLoopProtocol);
  auto now = std::chrono::steady_clock::now();
  // Expire stale forwarded-request entries (a superseded request never
  // produces a reply here) after 10 timeouts.
  for (auto it = waiting_requests_.begin(); it != waiting_requests_.end();) {
    if (now - it->second > std::chrono::milliseconds(10 * vc_timeout_ms_)) {
      it = waiting_requests_.erase(it);
    } else {
      ++it;
    }
  }
  if (replica_->awaiting_state()) {
    // A lagging replica waiting on state transfer retries the fetch on the
    // timer — a view change would not help it catch up. Dedicated deadline:
    // the VC timer may hold a stale backed-off deadline.
    timer_armed_ = false;
    if (!state_timer_armed_) {
      state_timer_armed_ = true;
      state_timer_deadline_ = now + std::chrono::milliseconds(vc_timeout_ms_);
      return;
    }
    if (now < state_timer_deadline_) return;
    emit(replica_->retry_state_transfer());
    state_timer_armed_ = false;
    return;
  }
  state_timer_armed_ = false;
  bool pending = !waiting_requests_.empty() || replica_->has_unexecuted();
  if (!pending) {
    timer_armed_ = false;
    timer_backoff_ = 1;
    timer_retransmitted_ = false;
    observe_backoff_level();
    return;
  }
  if (!timer_armed_) {
    timer_armed_ = true;
    // Tentative mode: progress = COMMITTED sequences, so a
    // commit-starved cluster still escalates (tentative executions roll
    // back — they must not placate the timer).
    timer_exec_snapshot_ = replica_->progress_marker();
    timer_view_snapshot_ = replica_->view();
    timer_deadline_ =
        now + std::chrono::milliseconds(vc_timeout_ms_ * timer_backoff_);
    return;
  }
  if (now < timer_deadline_) return;
  if (replica_->progress_marker() > timer_exec_snapshot_ ||
      replica_->view() > timer_view_snapshot_) {
    // Progress happened; rearm fresh.
    timer_backoff_ = 1;
    timer_retransmitted_ = false;
  } else if (replica_->in_view_change() && !timer_retransmitted_) {
    // First no-progress expiry while a view change pends (ISSUE 12):
    // re-broadcast the pending VIEW-CHANGE verbatim instead of
    // escalating — a lost VIEW-CHANGE/NEW-VIEW recovers in the SAME
    // view (the primary-elect answers a retransmitted VIEW-CHANGE with
    // its cached NEW-VIEW). Only the NEXT expiry escalates.
    timer_retransmitted_ = true;
    {
      FlightRecorder& fl = global_flight();
      if (fl.enabled()) {
        fl.record(kFlightViewTimerFired, replica_->view(), timer_backoff_,
                  -1);
      }
    }
    if (trace_fp_) {
      std::fprintf(trace_fp_,
                   "{\"ts\":%.6f,\"ev\":\"view_timer_fired\",\"replica\":"
                   "%lld,\"view\":%lld,\"backoff\":%d}\n",
                   trace_now(), (long long)id_, (long long)replica_->view(),
                   timer_backoff_);
      std::fflush(trace_fp_);
    }
    emit(replica_->retransmit_view_change());
  } else {
    // No progress within the timeout (again): suspect the primary.
    // Exponential backoff keeps cascading view changes from thrashing
    // (§4.5.2).
    timer_backoff_ = std::min(timer_backoff_ * 2, 64);
    timer_retransmitted_ = false;
    metrics_.inc("pbft_view_changes_total");
    // The view-change span opens here (ROADMAP item 4): timer fired ->
    // view_change_sent (Replica::view_hook) -> new_view_installed.
    {
      FlightRecorder& fl = global_flight();
      if (fl.enabled()) {
        fl.record(kFlightViewTimerFired, replica_->view(), timer_backoff_,
                  -1);
      }
    }
    if (trace_fp_) {
      std::fprintf(trace_fp_,
                   "{\"ts\":%.6f,\"ev\":\"view_timer_fired\",\"replica\":"
                   "%lld,\"view\":%lld,\"backoff\":%d}\n",
                   trace_now(), (long long)id_, (long long)replica_->view(),
                   timer_backoff_);
      std::fflush(trace_fp_);
    }
    trace_view_change(timer_backoff_);
    emit(replica_->start_view_change());
  }
  observe_backoff_level();
  timer_armed_ = false;  // rearmed on the next tick while work pends
}

void ReplicaServer::observe_backoff_level() {
  if (timer_backoff_ == gauged_backoff_) return;
  gauged_backoff_ = timer_backoff_;
  metrics_.set_gauge("pbft_view_timer_backoff_level", (double)timer_backoff_);
  FlightRecorder& fl = global_flight();
  if (fl.enabled()) {
    fl.record(kFlightBackoffLevel, replica_->view(), timer_backoff_, -1);
  }
}

int ReplicaServer::peer_fd(int64_t dest) {
  auto it = peers_.find(dest);
  if (it != peers_.end()) {
    if (!it->second->closed) return it->second->fd;
    // A conn that closed THIS poll iteration may still be referenced by
    // poll_once's order[] snapshot — replacing it here would free a Conn
    // the loop still dereferences (use-after-free). Defer the redial to
    // the next iteration (after the closed entry is swept); the dropped
    // message is retransmission-covered, as any PBFT loss is.
    return -1;
  }
  std::string addr = peer_addr(dest);
  if (addr.empty()) return -1;  // discovery hasn't named this peer yet
  bool in_progress = false;
  int fd = dial_tcp_nb(addr, &in_progress);
  if (fd < 0) return -1;
  auto c = std::make_unique<Conn>();
  c->fd = fd;
  c->peer_dest = dest;
  c->connecting = in_progress;
  c->connect_deadline =
      std::chrono::steady_clock::now() + std::chrono::seconds(10);
  // Link prologue: every peer link opens with a version-carrying hello;
  // secure clusters start the full handshake (protocol messages queue in
  // c->pending until it completes). Authenticator mode on a plaintext
  // cluster runs the SAME handshake auth-only (lane keys + identity,
  // frames stay plaintext); an old responder downgrades the link in
  // handle_peer_frame.
  c->rbuf.data = pool_.acquire();
  if (cfg_.secure || fastpath_mac_) {
    c->chan = std::make_unique<SecureChannel>(&cfg_, id_, seed_,
                                              /*initiator=*/true, dest,
                                              fastpath_mac_,
                                              /*auth_only=*/!cfg_.secure);
    queue_bytes(*c, frame_payload(c->chan->initiator_hello()));
  } else {
    queue_bytes(*c, frame_payload(SecureChannel::plain_hello(id_)));
  }
  register_conn(*c);
  peers_[dest] = std::move(c);
  return fd;
}

void ReplicaServer::send_to(int64_t dest, const Message& m) {
  if (dest == id_) {
    // Self-delivery bypasses the wire AND the fault modes: a Byzantine
    // replica trusts its own messages; only its peers see the behavior.
    emit(in_protocol([&] { return replica_->receive(m); }));
    return;
  }
  if (fault_mode_ == FaultMode::kMute) {
    count_fault();
    return;
  }
  Message corrupted;
  const Message* mp = &m;
  if (fault_mode_ == FaultMode::kSigCorrupt) {
    corrupted = corrupt_sig(m);
    mp = &corrupted;
    count_fault();
  }
  if (shards_) {
    // Point-to-point send: no broadcast-encode accounting (null tally),
    // matching the single-loop path below.
    std::string addr = peer_addr(dest);
    if (!addr.empty()) {
      shards_->send_peer(dest, addr,
                         std::make_shared<ShardEncoded>(*mp, nullptr));
    }
    return;
  }
  EncodedOut enc(mp);
  send_encoded(dest, enc);
}

void ReplicaServer::send_encoded(int64_t dest, EncodedOut& enc) {
  if (chaos_drop_pct_ > 0 &&
      std::uniform_real_distribution<double>()(chaos_rng_) < chaos_drop_pct_) {
    // Seeded link loss (--chaos-drop-pct): the frame never leaves this
    // replica. PBFT's retransmission paths must absorb it.
    ++chaos_dropped_;
    metrics_.inc("pbft_chaos_dropped_total");
    return;
  }
  if (peer_fd(dest) < 0) return;  // peer down: PBFT tolerates f of these
  Conn& c = *peers_[dest];
  const std::string* payload = nullptr;
  bool mac_frame = false;
  if (c.mac_ready) {
    // Authenticator mode: the shared MAC-vector frame — one encode +
    // one lane set per broadcast, every mac link ships the same bytes.
    payload = enc.mac_payload(mac_send_keys_);
    mac_frame = payload != nullptr;
  }
  if (payload == nullptr && c.codec_binary) payload = enc.binary_payload();
  if (payload == nullptr) payload = &enc.json_payload();
  if (mac_frame) ++mac_frames_;  // pbft_mac_frames_total: folded at the scrape
  if (c.chan && !c.chan->established()) {
    // Handshake in flight: queue (bounded — a wedged handshake must not
    // buffer without limit; PBFT tolerates the loss via retransmission).
    if (c.pending.size() < 4096) c.pending.push_back(*payload);
    flush(c);
    return;
  }
  if (c.chan && !c.chan->auth_only()) {
    // Bounded-outbound admission BEFORE the seal: sealing consumes the
    // link's AEAD nonce, so a post-seal drop would desync the channel —
    // the admission drop must look like the frame was never sealed.
    if (!outbound_has_room(c)) return;  // drop-and-count, like a link drop
    // Per-peer sealing over the SHARED plaintext: the AEAD counter is
    // per-link state, so only the seal (not the encode) runs per peer.
    std::string framed = frame_payload(c.chan->seal_frame(*payload));
    if (!chaos_pass(dest, framed)) return;
    queue_bytes(c, framed);
  } else {
    std::string framed = frame_payload(*payload);
    if (!chaos_pass(dest, framed)) return;
    if (!outbound_has_room(c)) return;
    queue_bytes(c, framed);
  }
  flush_once_an_emit(c);
}

bool ReplicaServer::chaos_pass(int64_t dest, const std::string& framed) {
  if (chaos_delay_ms_ <= 0) return true;
  // Per-destination FIFO: frames release in the order they were sealed,
  // so the delay reorders ACROSS links (and against local processing) but
  // never within one link — a secure channel's AEAD nonces stay in
  // sequence. The release jitter is drawn from the seeded chaos RNG.
  int jitter = (int)(std::uniform_real_distribution<double>()(chaos_rng_) *
                     (double)chaos_delay_ms_);
  chaos_queue_[dest].push_back(
      {std::chrono::steady_clock::now() + std::chrono::milliseconds(jitter),
       framed});
  return false;
}

void ReplicaServer::pump_chaos_queue(
    std::chrono::steady_clock::time_point now) {
  if (chaos_queue_.empty()) return;
  for (auto it = chaos_queue_.begin(); it != chaos_queue_.end();) {
    auto& q = it->second;
    while (!q.empty() && q.front().first <= now) {
      auto p = peers_.find(it->first);
      if (p != peers_.end() && !p->second->closed &&
          !p->second->connecting) {
        // Unconditional enqueue: these frames passed admission (and were
        // sealed) at send time — a bounded-outbound drop HERE would
        // desync a secure link's AEAD nonce sequence.
        queue_bytes(*p->second, q.front().second);
        flush(*p->second);
      } else {
        // Link died while the frame was held: the delay became a drop.
        ++chaos_dropped_;
        metrics_.inc("pbft_chaos_dropped_total");
      }
      q.pop_front();
    }
    it = q.empty() ? chaos_queue_.erase(it) : std::next(it);
  }
}

// Remember which gateway link forwarded for `client`: the exact-route
// half of the reply fan-back. Bounded — on overflow the cache clears and
// un-routed "gw/" replies fall back to a fan-out over all gateway links
// (extra frames, never lost quorums).
void ReplicaServer::note_gateway_route(const std::string& client,
                                       uint64_t link_id) {
  if (gateway_routes_.size() >= kMaxGatewayRoutes) gateway_routes_.clear();
  gateway_routes_[client] = link_id;
}

// Route a reply back over a gateway link: one framed raw-JSON payload on
// the SAME persistent connection the request came in on — the whole
// point of the tier (no per-reply dial-back, no per-client socket).
void ReplicaServer::send_gateway_reply(Conn& g, const std::string& payload) {
  if (g.closed || !outbound_has_room(g)) return;  // drop-and-count
  queue_bytes(g, frame_payload(payload));
  flush_once_an_emit(g);
}

void ReplicaServer::dial_reply(const std::string& client_addr,
                               const ClientReply& reply) {
  // Dial back to the client's advertised address (the reference's contract,
  // reference src/client_handler.rs:75-84): raw JSON + newline, then close.
  // The client address is UNTRUSTED input — the dial is nonblocking and
  // deadline-bounded so an unroutable address cannot stall the event loop
  // (the reference dialed synchronously, src/client_handler.rs:75-84).
  ClientReply out = reply;
  // The Byzantine signer corrupts EVERY outgoing signature — dial-back
  // replies included, matching the simulation mutator (bench/harness.py)
  // and net.h's contract: this replica's reply vote must not count at the
  // client's f+1 signature-verified quorum.
  if (fault_mode_ == FaultMode::kSigCorrupt && !out.sig.empty()) {
    out.sig.assign(out.sig.size(), 'f');
    count_fault();
  }
  send_client_line(client_addr, out.to_json().dump());
}

void ReplicaServer::send_client_line(const std::string& client_addr,
                                     const std::string& payload) {
  if (shards_) {
    // Multi-core mode: gateway links live in their shards; the route
    // table stores packed (shard, token) keys. Same policy as below —
    // exact route, else fan out over every live gateway link, else the
    // retransmission path re-fetches the cached reply. Non-gateway
    // addresses dial back from a shard picked by address hash (keeps the
    // one-in-flight-per-address invariant within one shard).
    if (client_addr.compare(0, 3, kGatewayClientPrefix) == 0) {
      auto rt = gateway_routes_.find(client_addr);
      if (rt != gateway_routes_.end()) {
        if (sharded_gateways_.count(rt->second)) {
          shards_->send_gateway_line((int)(rt->second >> 48),
                                     rt->second & ((1ull << 48) - 1),
                                     payload);
          return;
        }
        gateway_routes_.erase(rt);  // link died: fall through to fan-out
      }
      if (sharded_gateways_.empty()) {
        ++replies_dropped_;
        return;
      }
      for (uint64_t key : sharded_gateways_) {
        shards_->send_gateway_line((int)(key >> 48),
                                   key & ((1ull << 48) - 1), payload);
      }
      return;
    }
    shards_->dial_reply(client_addr, payload + "\n");
    return;
  }
  if (client_addr.compare(0, 3, kGatewayClientPrefix) == 0) {
    // Gateway-routed client (ISSUE 10): the "address" is a routing
    // token, never dialable. Exact route when this replica saw the
    // request arrive on a gateway link; otherwise fan out over every
    // gateway link (gateways drop tokens they don't own) — a backup
    // that only saw the request via pre-prepare still reaches the
    // client's gateway for the f+1 reply quorum.
    auto rt = gateway_routes_.find(client_addr);
    if (rt != gateway_routes_.end()) {
      auto g = gateway_links_.find(rt->second);
      if (g != gateway_links_.end()) {
        send_gateway_reply(*g->second, payload);
        return;
      }
      gateway_routes_.erase(rt);  // link died: fall through to fan-out
    }
    if (gateway_links_.empty()) {
      ++replies_dropped_;  // retransmission re-fetches the cached reply
      return;
    }
    for (auto& [_, g] : gateway_links_) send_gateway_reply(*g, payload);
    return;
  }
  start_reply_dial(client_addr, payload + "\n");
}

bool ReplicaServer::maybe_reject_overload(const ClientRequest& req) {
  if (cfg_.admission_inflight <= 0 && cfg_.admission_backlog <= 0)
    return false;
  const int64_t last = replica_->client_last_timestamp(req.client);
  if (req.timestamp <= last) return false;  // retransmission: cache answers
  bool reject = cfg_.admission_inflight > 0 &&
                req.timestamp - last > cfg_.admission_inflight;
  if (!reject && cfg_.admission_backlog > 0) {
    const int64_t backlog =
        (int64_t)replica_->pending_count() + replica_->seal_backlog();
    reject = backlog > cfg_.admission_backlog;
  }
  if (!reject) return false;
  ++overload_rejections_;
  metrics_.inc("pbft_overload_rejections_total");
  {
    FlightRecorder& fl = global_flight();
    if (fl.enabled()) {
      fl.record(kFlightOverloadRejected, replica_->view(), req.timestamp, -1);
    }
  }
  // Explicit overloaded line toward the client (mirrors net/server.py).
  // Built via Json (never format-string field literals): the metrics
  // lint reads net.cc's escaped-quote tokens as trace-event fields.
  JsonObject o;
  o["type"] = Json(std::string("overloaded"));
  o["client"] = Json(req.client);
  o["timestamp"] = Json(req.timestamp);
  o["replica"] = Json(id_);
  send_client_line(req.client, Json(o).dump());
  return true;
}

// At most this many one-shot reply dials in flight: a pipelined burst can
// emit dozens of replies in one loop iteration, and firing them all at
// once overflows small client accept backlogs (the blocking dial this
// replaced was accidentally self-pacing). Excess replies queue and launch
// as slots free.
static constexpr size_t kMaxReplyDialsInFlight = 8;
static constexpr size_t kMaxReplyBacklog = 10000;

bool ReplicaServer::reply_budget_free() const {
  return reply_dials_in_flight_ < kMaxReplyDialsInFlight;
}

// A failed dial drops the reply: the client's retransmission rule
// re-fetches the cached reply (PBFT §4.1), so loss here is safe.
void ReplicaServer::reply_dial_now(const std::string& addr,
                                   std::string payload) {
  bool in_progress = false;
  int fd = dial_tcp_nb(addr, &in_progress);
  if (fd < 0) return;
  auto c = std::make_unique<Conn>();
  c->fd = fd;
  c->connecting = in_progress;
  // Short deadline: these addresses are UNTRUSTED client input, and each
  // black-holed dial pins an in-flight slot until reaped — 3s covers a
  // legitimate listener's SYN retry while bounding the head-of-line harm.
  c->connect_deadline =
      std::chrono::steady_clock::now() + std::chrono::seconds(3);
  c->close_when_flushed = true;
  c->reply_addr = addr;
  c->rbuf.data = pool_.acquire();
  queue_bytes(*c, payload);
  ++reply_dials_in_flight_;  // mark_closed decrements on every close path
  reply_addrs_in_flight_.insert(addr);
  register_conn(*c);
  flush(*c);
  if (!c->closed) conns_.push_back(std::move(c));
}

// Queued replies older than this are dropped (counted): with all
// in-flight slots pinned by black-holed addresses, an honest reply must
// not sit in FIFO order for minutes — the client retransmits well before
// this and the cached reply re-enters the queue near the front.
static constexpr auto kReplyBacklogTtl = std::chrono::seconds(5);

void ReplicaServer::start_reply_dial(const std::string& addr,
                                     std::string payload) {
  if (reply_budget_free() && !reply_addrs_in_flight_.count(addr)) {
    reply_dial_now(addr, std::move(payload));
  } else if (reply_backlog_.size() < kMaxReplyBacklog) {
    reply_backlog_.push_back(QueuedReply{addr, std::move(payload),
                                         std::chrono::steady_clock::now()});
  } else {
    ++replies_dropped_;  // observable via metrics_json
  }
}

void ReplicaServer::pump_reply_backlog() {
  if (reply_backlog_.empty()) return;
  LoopClock::Scope in(loop_clock_, kLoopSend);
  // Per-entry scan (no head-of-line blocking): TTL-expired entries drop,
  // entries whose address already has a dial in flight stay queued, the
  // rest launch while the budget lasts.
  auto now = std::chrono::steady_clock::now();
  std::deque<QueuedReply> keep;
  while (!reply_backlog_.empty()) {
    auto entry = std::move(reply_backlog_.front());
    reply_backlog_.pop_front();
    if (now - entry.enqueued > kReplyBacklogTtl) {
      ++replies_dropped_;
      continue;
    }
    if (!reply_budget_free()) {
      keep.push_back(std::move(entry));
      while (!reply_backlog_.empty()) {  // budget gone: keep the rest as-is
        keep.push_back(std::move(reply_backlog_.front()));
        reply_backlog_.pop_front();
      }
      break;
    }
    if (reply_addrs_in_flight_.count(entry.addr)) {
      keep.push_back(std::move(entry));
      continue;
    }
    reply_dial_now(entry.addr, std::move(entry.payload));
  }
  reply_backlog_ = std::move(keep);
}

namespace {

// Resident set in bytes from /proc/self/statm field 2 (pages). Returns 0
// where /proc is absent — the detectors treat a zero reading as "no
// data", never as a leak baseline.
int64_t read_rss_bytes() {
  FILE* f = std::fopen("/proc/self/statm", "r");
  if (!f) return 0;
  long long vm_pages = 0, rss_pages = 0;
  int got = std::fscanf(f, "%lld %lld", &vm_pages, &rss_pages);
  std::fclose(f);
  if (got != 2) return 0;
  return (int64_t)rss_pages * (int64_t)sysconf(_SC_PAGESIZE);
}

// Open file descriptors via /proc/self/fd (the dirfd the walk itself
// holds is excluded). Returns 0 where /proc is absent.
int64_t count_open_fds() {
  DIR* d = opendir("/proc/self/fd");
  if (!d) return 0;
  int64_t n = 0;
  while (struct dirent* e = readdir(d)) {
    if (e->d_name[0] != '.') ++n;
  }
  closedir(d);
  return n > 0 ? n - 1 : 0;  // minus the opendir fd
}

int64_t file_size_bytes(const std::string& path) {
  if (path.empty()) return 0;
  struct stat st{};
  return ::stat(path.c_str(), &st) == 0 ? (int64_t)st.st_size : 0;
}

}  // namespace

void ReplicaServer::fold_counters() {
  // Up to this instant, for /status too (which reads the clock itself).
  if (loop_clock_.on) loop_clock_.sync();
  if (!metrics_.enabled) return;
  fold_delta(event_wakeups_ + (shards_ ? shards_->total_wakeups() : 0),
             &seen_wakeups_, "pbft_epoll_wakeups_total");
  fold_delta(frames_in_, &seen_frames_in_, "pbft_frames_in_total");
  fold_delta(mac_frames_ + (shards_ ? shards_->mac_frames() : 0),
             &seen_mac_frames_, "pbft_mac_frames_total");
  fold_delta(gateway_forwarded_, &seen_gateway_forwarded_,
             "pbft_gateway_forwarded_total");
  fold_delta(frames_out_ + (shards_ ? shards_->frames_out() : 0),
             &seen_frames_out_, "pbft_frames_out_total");
  fold_delta(send_calls_ + (shards_ ? shards_->send_calls() : 0),
             &seen_send_calls_, "pbft_send_calls_total");
  // The loop clock, in whole microseconds a stage (pbft_loop_<stage>_us_total);
  // the total is the sum of the seven AS FOLDED, so the eight counters
  // agree to the microsecond.
  int64_t total_us = 0;
  for (int i = 0; i < kLoopStages; ++i) {
    char name[48];
    std::snprintf(name, sizeof(name), "pbft_loop_%s_us_total",
                  kLoopStageNames[i]);
    const int64_t us = loop_clock_.ns[i] / 1000;
    fold_delta(us, &seen_loop_us_[i], name);
    total_us += us;
  }
  fold_delta(total_us, &seen_loop_total_us_, "pbft_loop_us_total");
  if (!shards_) return;
  // The front-end threads' clocks (ISSUE 40), summed over this replica's
  // shards and over its pipelines, as each thread last published them.
  for (int i = 0; i < kFrontStages; ++i) {
    char name[48];
    std::snprintf(name, sizeof(name), "pbft_shard_%s_us_total",
                  kShardStageNames[i]);
    fold_delta(shards_->front_stage_us(false, i), &seen_shard_us_[i], name);
    std::snprintf(name, sizeof(name), "pbft_pipe_%s_us_total",
                  kPipeStageNames[i]);
    fold_delta(shards_->front_stage_us(true, i), &seen_pipe_us_[i], name);
  }
}

void ReplicaServer::fold_delta(int64_t now_abs, int64_t* seen,
                               const char* name) {
  if (now_abs > *seen) {
    metrics_.inc(name, now_abs - *seen);
    *seen = now_abs;
  }
}

void ReplicaServer::refresh_health() {
  fold_counters();
  const auto now = std::chrono::steady_clock::now();
  const int64_t executed = replica_->executed_upto();
  if (executed != progress_seen_executed_) {
    progress_seen_executed_ = executed;
    progress_seen_at_ = now;
  }
  if (!metrics_.enabled) return;
  const double since =
      std::chrono::duration<double>(now - progress_seen_at_).count();
  metrics_.set_gauge("pbft_process_rss_bytes", (double)read_rss_bytes());
  metrics_.set_gauge("pbft_open_fds", (double)count_open_fds());
  metrics_.set_gauge("pbft_wal_disk_bytes",
                     (double)file_size_bytes(wal_path_));
  metrics_.set_gauge("pbft_last_progress_seconds", since);
  metrics_.set_gauge("pbft_inbox_depth", (double)replica_->pending_count());
}

std::string ReplicaServer::metrics_json() {
  refresh_health();
  JsonObject o;
  o["replica"] = Json(id_);
  o["port"] = Json(listen_port_);
  o["net_backend"] = Json(std::string(poller_->name()));
  o["frames_in"] = Json(frames_in_);
  // Multi-core surface (ISSUE 13): loop-thread count, aggregate crypto
  // offload queue depth, cross-thread wake count, and the per-shard
  // wakeup attribution for pbft_epoll_wakeups_total.
  o["net_threads"] = Json(shards_ ? (int64_t)shards_->n_shards() : 1);
  o["cross_thread_wakes"] =
      Json(shards_ ? shards_->cross_thread_wakes() : 0);
  o["crypto_offload_queue_depth"] =
      Json(shards_ ? shards_->crypto_queue_depth() : 0);
  if (shards_) {
    JsonArray sw;
    for (int i = 0; i < shards_->n_shards(); ++i) {
      sw.push_back(Json(shards_->shard_wakeups(i)));
    }
    o["shard_wakeups"] = Json(std::move(sw));
    // Each shard thread's and each pipeline thread's microseconds by kind
    // of work (net_shard.h FrontClock), and what was lost at a thread
    // boundary, by kind: a healthy run reads 0 of each.
    JsonArray shard_us, pipe_us;
    for (int k = 0; k < shards_->n_shards(); ++k) {
      JsonObject su, pu;
      for (int i = 0; i < kFrontStages; ++i) {
        su[kShardStageNames[i]] = Json(shards_->front_stage_us(false, k, i));
        pu[kPipeStageNames[i]] = Json(shards_->front_stage_us(true, k, i));
      }
      shard_us.push_back(Json(std::move(su)));
      pipe_us.push_back(Json(std::move(pu)));
    }
    o["shard_us"] = Json(std::move(shard_us));
    o["pipe_us"] = Json(std::move(pipe_us));
    // Drains of the shard inbox that found something and the seconds
    // their oldest entries had waited (pbft_shard_handoff_seconds' count
    // and sum).
    JsonObject handoff;
    handoff["drains"] = Json(shard_handoffs_);
    handoff["seconds"] = Json(shard_handoff_s_);
    o["shard_handoff"] = Json(std::move(handoff));
    JsonObject dropped;
    dropped["pipeline"] = Json(shards_->pipeline_dropped());
    dropped["inbox"] = Json(shards_->inbox_dropped());
    dropped["replies"] = Json(shards_->replies_dropped());
    o["shard_dropped"] = Json(std::move(dropped));
  }
  o["connections_open"] =
      Json(shards_ ? shards_->connections_open()
                   : (int64_t)(conns_.size() + peers_.size()));
  o["event_wakeups"] =
      Json(event_wakeups_ + (shards_ ? shards_->total_wakeups() : 0));
  o["backpressure_events"] =
      Json(backpressure_events_ +
           (shards_ ? shards_->backpressure_events() : 0));
  o["gateway_links"] =
      Json((int64_t)(shards_ ? sharded_gateways_.size()
                             : gateway_links_.size()));
  o["gateway_forwarded"] = Json(gateway_forwarded_);
  // Perf-under-faults surface (ISSUE 12).
  o["overload_rejections"] = Json(overload_rejections_);
  o["gateway_failovers"] = Json(gateway_failovers_);
  o["view_timer_backoff"] = Json((int64_t)timer_backoff_);
  o["verify_batches"] = Json(batches_run_);
  o["verify_launched_ahead"] = Json(launched_ahead_);
  {
    // Kept spans worked through so far and the seconds that took
    // (pbft_verdict_apply_seconds' count and sum).
    JsonObject apply;
    apply["batches"] = Json(verdict_applies_);
    apply["seconds"] = Json(verdict_apply_s_);
    o["verify_apply"] = Json(std::move(apply));
    // The loop thread's microseconds by kind of work, the passes they
    // were spent in and the stage switches that measured them.
    JsonObject loop;
    for (int i = 0; i < kLoopStages; ++i) {
      loop[kLoopStageNames[i]] = Json(loop_clock_.ns[i] / 1000);
    }
    loop["passes"] = Json(event_wakeups_);
    loop["switches"] = Json(loop_clock_.switches);
    o["loop_us"] = Json(std::move(loop));
  }
  o["broadcasts"] = Json(broadcasts_);
  o["broadcast_encodes"] =
      Json(broadcast_encodes_ +
           (shards_ ? shards_->broadcast_encodes() : 0));
  o["reply_backlog"] = Json((int64_t)reply_backlog_.size());
  o["replies_dropped"] = Json(replies_dropped_);
  o["faults_injected"] = Json(faults_injected_);
  o["chaos_dropped"] =
      Json(chaos_dropped_ + (shards_ ? shards_->chaos_dropped() : 0));
  o["verify_deadline_fired"] = Json(verify_deadline_fired_);
  // Batches verified on the host although a verify service is configured
  // (warming, unreachable, killed mid-stream, past its deadline): the
  // liveness fallback, counted so it cannot hide a dead device.
  o["verify_service_fallbacks"] = Json(verify_service_fallbacks());
  // Fast-path surface (ISSUE 14): the negotiated-offer mode, tentative
  // execution, MAC frame tallies, committed floor.
  o["mode"] = Json(std::string(fastpath_mac_ ? "mac" : "sig"));
  o["tentative"] = Json(cfg_.tentative);
  o["mac_frames"] =
      Json(mac_frames_ + (shards_ ? shards_->mac_frames() : 0));
  o["mac_rejected"] =
      Json(mac_rejected_ + (shards_ ? shards_->mac_rejected() : 0));
  // Durable-recovery surface (ISSUE 15).
  o["wal_enabled"] = Json((bool)wal_);
  o["recovered_from_wal"] = Json(recovered_from_wal_);
  o["wal_appends"] = Json(wal_ ? wal_->appends() : 0);
  o["wal_fsyncs"] = Json(wal_ ? wal_->fsyncs() : 0);
  o["wal_bytes"] = Json(wal_ ? wal_->bytes_written() : 0);
  o["committed_upto"] = Json(replica_->committed_upto());
  o["executed_upto"] = Json(replica_->executed_upto());
  o["low_mark"] = Json(replica_->low_mark());
  o["view"] = Json(replica_->view());
  o["in_view_change"] = Json(replica_->in_view_change());
  // Health document (ISSUE 16; shape contracted with server.py by
  // kHealthDocVersion): resource readings, progress watermarks, and the
  // identity digests the divergence detector compares. The progress
  // clock is quantized to the refresh cadence (see refresh_health).
  const auto now = std::chrono::steady_clock::now();
  o["health_version"] = Json(kHealthDocVersion);
  o["uptime_seconds"] =
      Json(std::chrono::duration<double>(now - start_time_).count());
  o["rss_bytes"] = Json(read_rss_bytes());
  o["open_fds"] = Json(count_open_fds());
  o["wal_disk_bytes"] = Json(file_size_bytes(wal_path_));
  o["inbox_depth"] = Json((int64_t)replica_->pending_count());
  o["sealed_unexecuted"] = Json(replica_->seal_backlog());
  o["waiting_requests"] = Json((int64_t)waiting_requests_.size());
  o["last_progress_seconds"] =
      Json(std::chrono::duration<double>(now - progress_seen_at_).count());
  o["chain_digest"] = Json(replica_->committed_chain_hex());
  o["state_digest"] = Json(replica_->state_digest_hex());
  for (const auto& [k, v] : replica_->counters) o[k] = Json(v);
  return Json(o).dump();
}

}  // namespace pbft
