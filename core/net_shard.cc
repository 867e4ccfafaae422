// Multi-core replica front end (ISSUE 13) — see net_shard.h for the
// thread/ownership model. Everything here runs OFF the consensus thread:
// NetShard methods on their shard's loop thread, CryptoPipeline methods
// on their pipeline thread, and the NetShards entry points marked
// "consensus-thread" in net_shard.h on the consensus thread (they only
// touch the queues and relaxed atomics).
#include "net_shard.h"

#include <arpa/inet.h>
#include <fcntl.h>
#include <netinet/in.h>
#include <netinet/tcp.h>
#include <poll.h>
#include <sys/socket.h>
#include <unistd.h>
#ifdef __linux__
#include <sys/eventfd.h>
#endif

#include <algorithm>
#include <cerrno>
#include <cstdio>
#include <cstring>
#include <functional>

namespace pbft {

namespace {

// Shard-poller sentinel tags (Conn tags are heap pointers, never small).
constexpr uint64_t kShardTagListener = 1;
constexpr uint64_t kShardTagWake = 2;

// Reply-dial pacing, per shard (the single-loop policy in net.cc, applied
// per shard by design: each shard paces its own one-shot dials — the
// ISSUE 13 satellite that makes reply bookkeeping per-shard).
constexpr size_t kShardMaxReplyDials = 8;
constexpr size_t kShardMaxReplyBacklog = 10000;
constexpr auto kShardReplyBacklogTtl = std::chrono::seconds(5);
// Pre-handshake pending payloads per peer link (mirrors net.cc's 4096).
constexpr size_t kMaxPendingPerPeer = 4096;

void shard_set_nonblocking(int fd) {
  int flags = fcntl(fd, F_GETFL, 0);
  fcntl(fd, F_SETFL, flags | O_NONBLOCK);
}

}  // namespace

// -- WakeFd ------------------------------------------------------------------

WakeFd::~WakeFd() {
  if (rfd_ >= 0) close(rfd_);
  if (wfd_ >= 0 && wfd_ != rfd_) close(wfd_);
}

bool WakeFd::open_fds() {
#ifdef __linux__
  rfd_ = eventfd(0, EFD_CLOEXEC | EFD_NONBLOCK);
  if (rfd_ >= 0) {
    wfd_ = rfd_;
    return true;
  }
#endif
  int fds[2];
  if (pipe(fds) != 0) return false;
  shard_set_nonblocking(fds[0]);
  shard_set_nonblocking(fds[1]);
  rfd_ = fds[0];
  wfd_ = fds[1];
  return true;
}

void WakeFd::wake() {
  // Coalesce: one write per un-drained episode. A producer that finds
  // signaled_ set leaves its item to the drain under way (see drain()).
  if (signaled_.exchange(true, std::memory_order_acq_rel)) return;
  wakes_.fetch_add(1, std::memory_order_relaxed);
  const uint64_t one = 1;
  (void)!write(wfd_, &one, sizeof(one));
}

void WakeFd::drain() {
  // The ORDER is the invariant: empty the fd, THEN clear signaled_, THEN
  // (the caller) drain the queues. A producer whose exchange comes before
  // the clear pushed before it too, and the queue drain that follows the
  // clear takes its item; one whose exchange comes after the clear writes
  // the fd afresh, and nothing reads the fd again before the next wait.
  // Clearing first (the order up to PR 39) let this read() swallow the
  // write of a producer that saw the cleared flag: signaled_ then stood
  // true over an empty fd and every later wake() returned early — the
  // consumer was never woken again.
  uint64_t buf[16];
  while (read(rfd_, buf, sizeof(buf)) > 0) {
  }
  signaled_.store(false, std::memory_order_release);
}

// -- ShardEncoded ------------------------------------------------------------

const std::string& ShardEncoded::json_payload() {
  std::lock_guard<std::mutex> lk(mu_);
  if (!json_done_) {
    json_done_ = true;
    json_ = message_canonical(m_);
    if (tally_) tally_->fetch_add(1, std::memory_order_relaxed);
  }
  return json_;
}

const std::string* ShardEncoded::binary_payload() {
  std::lock_guard<std::mutex> lk(mu_);
  if (!bin_tried_) {
    bin_tried_ = true;
    bin_ok_ = message_to_binary(m_, &binary_);
    if (bin_ok_ && tally_) tally_->fetch_add(1, std::memory_order_relaxed);
  }
  return bin_ok_ ? &binary_ : nullptr;
}

const std::string* ShardEncoded::mac_payload(NetShards* owner) {
  std::lock_guard<std::mutex> lk(mu_);
  if (!mac_tried_) {
    mac_tried_ = true;
    auto keys = owner->mac_key_snapshot();
    if (!keys.empty()) {
      uint8_t signable[32];
      message_signable(m_, signable);
      std::vector<MacLane> lanes;
      lanes.reserve(keys.size());
      for (const auto& [rid, key] : keys) {  // std::map: sorted lanes
        MacLane lane;
        lane.rid = rid;
        mac_tag(key.data(), signable, lane.tag);
        lanes.push_back(lane);
      }
      mac_ok_ = message_to_binary_mac(m_, lanes, &mac_);
      if (mac_ok_ && tally_) tally_->fetch_add(1, std::memory_order_relaxed);
    }
  }
  return mac_ok_ ? &mac_ : nullptr;
}

// -- CryptoPipeline ----------------------------------------------------------

void CryptoPipeline::push(CryptoCmd&& c, bool force) {
  bool accepted;
  {
    std::lock_guard<std::mutex> lk(mu_);
    if (!force && q_.size() >= 65536) {
      accepted = false;
    } else {
      q_.push_back(std::move(c));
      queue_depth.store((int64_t)q_.size(), std::memory_order_relaxed);
      accepted = true;
    }
  }
  if (!accepted) {
    drops.fetch_add(1, std::memory_order_relaxed);
    return;
  }
  cv_.notify_one();
}

void CryptoPipeline::notify() { cv_.notify_one(); }

void CryptoPipeline::run() {
  rng_.seed(chaos_seed);
  LoopClock& ck = clock.clock;
  while (!owner_->stopping()) {
    ck.set_on(owner_->clocks_on());
    {
      std::unique_lock<std::mutex> lk(mu_);
      if (q_.empty()) {
        LoopClock::Scope waiting(ck, kLoopWait);
        auto timeout = std::chrono::milliseconds(100);
        if (!chaos_queue_.empty()) {
          // A held chaos frame's release deadline bounds the sleep.
          auto now = std::chrono::steady_clock::now();
          auto earliest = now + timeout;
          for (const auto& [_, dq] : chaos_queue_) {
            if (!dq.empty()) earliest = std::min(earliest, dq.front().first);
          }
          auto rem = std::chrono::duration_cast<std::chrono::milliseconds>(
              earliest - now);
          timeout = std::max(std::chrono::milliseconds(1),
                             std::min(timeout, rem));
        }
        // wait_until on the SYSTEM clock, deliberately: wait_for (and
        // steady-clock wait_until) lower to pthread_cond_clockwait,
        // which older TSan runtimes do not intercept — the sanitizer
        // then never sees the mutex release inside the wait and every
        // later lock of mu_ reports as a false "double lock". The
        // system-clock path lowers to the intercepted
        // pthread_cond_timedwait; a clock jump at worst mistimes one
        // bounded (<= 100 ms) sleep.
        cv_.wait_until(lk, std::chrono::system_clock::now() + timeout);
      }
      local_.swap(q_);
      queue_depth.store(0, std::memory_order_relaxed);
    }
    clock.publish();  // the wait has just been charged: no clock read here
    for (auto& c : local_) handle(c);
    local_.clear();
    ck.enter(kLoopOther);
    pump_chaos(std::chrono::steady_clock::now());
  }
}

namespace {
// The pipeline stage a command is worked in.
constexpr int pipe_stage_of(CryptoCmd::Kind kind) {
  switch (kind) {
    case CryptoCmd::kInboundFrame:
    case CryptoCmd::kInboundLine:
      return kPipeDecode;
    case CryptoCmd::kSendPeer:
    case CryptoCmd::kSendClientLine:
      return kPipeEncode;
    default:
      return kLoopOther;
  }
}
}  // namespace

void CryptoPipeline::handle(CryptoCmd& c) {
  // The stage follows the command's kind and stays until a neighbour of
  // another kind: a run of inbound frames costs one clock read.
  clock.clock.enter(pipe_stage_of(c.kind));
  switch (c.kind) {
    case CryptoCmd::kInboundFrame:
      open_and_forward(c.conn_id, c.dest, std::move(c.bytes));
      return;
    case CryptoCmd::kInboundLine:
      parse_to_k(c.conn_id, false, std::move(c.bytes));
      return;
    case CryptoCmd::kConnEstablished: {
      if (c.dest >= 0) {
        PeerState& p = peers_[c.dest];
        p.ready = true;
        p.codec_binary = c.codec_binary;
        p.mac = c.mac;
        p.chan = std::move(c.chan);
        p.out_gauge = std::move(c.out_gauge);
        // Payloads queued while the prologue ran seal in FIFO order —
        // the nonce sequence starts exactly where the handshake left it.
        std::vector<std::string> pend;
        pend.swap(p.pending);
        for (auto& payload : pend) seal_and_ship(c.dest, payload);
      } else {
        ConnState& s = conns_[c.conn_id];
        s.chan = std::move(c.chan);
        s.mac = c.mac;
        s.gateway = c.gateway;
        s.out_gauge = std::move(c.out_gauge);
        if (c.gateway) {
          KInbound up;
          up.kind = KInbound::kGatewayUp;
          up.shard = idx_;
          up.conn_id = c.conn_id;
          owner_->push_inbound(idx_, std::move(up));
        }
      }
      return;
    }
    case CryptoCmd::kConnClosed: {
      if (c.dest >= 0) {
        peers_.erase(c.dest);  // pending lost: retransmission covers it
        return;
      }
      auto it = conns_.find(c.conn_id);
      if (it != conns_.end()) {
        if (it->second.gateway) {
          KInbound down;
          down.kind = KInbound::kGatewayDown;
          down.shard = idx_;
          down.conn_id = c.conn_id;
          owner_->push_inbound(idx_, std::move(down));
        }
        conns_.erase(it);
      }
      return;
    }
    case CryptoCmd::kSendPeer: {
      PeerState& p = peers_[c.dest];
      if (!p.ready) {
        // Link prologue still running (or first sight of this dest):
        // queue the canonical payload and make sure the shard is
        // dialing. Matches the single-loop pre-handshake pending queue.
        if (p.pending.size() < kMaxPendingPerPeer) {
          p.pending.push_back(c.enc->json_payload());
        } else {
          drops.fetch_add(1, std::memory_order_relaxed);
        }
        LoopCmd dial;
        dial.kind = LoopCmd::kDialPeer;
        dial.dest = c.dest;
        dial.addr = c.addr;
        owner_->shard(idx_).push(std::move(dial), /*force=*/true);
        return;
      }
      const std::string* payload = nullptr;
      bool mac_frame = false;
      if (p.mac) {
        // Authenticator mode (ISSUE 14): the shared MAC-vector frame —
        // lanes over the owner's cross-shard key table, computed at
        // most once per broadcast whichever pipeline gets there first.
        payload = c.enc->mac_payload(owner_);
        mac_frame = payload != nullptr;
      }
      if (payload == nullptr && p.codec_binary) {
        payload = c.enc->binary_payload();
      }
      if (payload == nullptr) payload = &c.enc->json_payload();
      if (mac_frame) mac_frames.fetch_add(1, std::memory_order_relaxed);
      seal_and_ship(c.dest, *payload);
      return;
    }
    case CryptoCmd::kSendClientLine: {
      auto it = conns_.find(c.conn_id);
      if (it == conns_.end()) return;  // gateway link died: fan-out covers
      auto& gauge = it->second.out_gauge;
      if (gauge &&
          (size_t)gauge->load(std::memory_order_relaxed) >
              max_conn_outbound()) {
        drops.fetch_add(1, std::memory_order_relaxed);
        return;
      }
      LoopCmd w;
      w.kind = LoopCmd::kWriteConn;
      w.conn_id = c.conn_id;
      w.bytes = frame_payload(c.bytes);
      owner_->shard(idx_).push(std::move(w), /*force=*/true);
      return;
    }
    case CryptoCmd::kDialReply: {
      LoopCmd d;
      d.kind = LoopCmd::kDialReply;
      d.addr = c.addr;
      d.bytes = std::move(c.bytes);
      owner_->shard(idx_).push(std::move(d), /*force=*/false);
      return;
    }
  }
}

void CryptoPipeline::open_and_forward(uint64_t conn_id, int64_t dest,
                                      std::string payload) {
  SecureChannel* chan = nullptr;
  bool from_gateway = false;
  if (dest >= 0) {
    auto it = peers_.find(dest);
    if (it == peers_.end()) return;  // closed before the frame drained
    chan = it->second.chan.get();
  } else {
    auto it = conns_.find(conn_id);
    if (it == conns_.end()) return;
    chan = it->second.chan.get();
    from_gateway = it->second.gateway;
  }
  if (chan && !chan->auth_only()) {
    auto pt = chan->open_frame(payload);
    if (!pt) {
      // AEAD failure: the link must drop (same contract as fail_conn).
      LoopCmd cl;
      cl.kind = LoopCmd::kCloseConn;
      cl.conn_id = conn_id;
      cl.dest = dest;
      owner_->shard(idx_).push(std::move(cl), /*force=*/true);
      if (dest >= 0) {
        peers_.erase(dest);
      } else {
        conns_.erase(conn_id);
      }
      return;
    }
    payload = std::move(*pt);
  }
  parse_to_k(conn_id, from_gateway, std::move(payload), chan);
}

void CryptoPipeline::parse_to_k(uint64_t conn_id, bool from_gateway,
                                std::string payload, SecureChannel* chan) {
  auto msg = from_payload(payload);
  if (!msg) return;
  KInbound in;
  in.kind = KInbound::kMsg;
  in.shard = idx_;
  in.conn_id = conn_id;
  in.from_gateway = from_gateway;
  // Authenticator fast path (ISSUE 14): a MAC frame on a mac-negotiated
  // link verifies OUR lane + the claimed sender here, on the pipeline
  // thread — the consensus thread then dispatches it with no verify
  // queue. A missing lane falls through to the signature path; a lane
  // mismatch drops and counts.
  if (chan && chan->established() && chan->mac_negotiated() &&
      payload_is_mac_frame(payload)) {
    uint8_t lane[16];
    if (mac_frame_lane(payload, owner_->id(), lane)) {
      uint8_t signable[32], want[16];
      message_signable_from_payload(payload, *msg, signable);
      mac_tag(chan->auth_recv_key(), signable, want);
      if (!mac_tag_equal(lane, want) ||
          mac_claimed_replica(*msg) != chan->peer_id()) {
        mac_rejected.fetch_add(1, std::memory_order_relaxed);
        return;
      }
      in.pre_authenticated = true;
      in.msg = std::move(*msg);
      owner_->push_inbound(idx_, std::move(in));
      return;
    }
  }
  if (!std::holds_alternative<ClientRequest>(*msg)) {
    // Receive-side canonical reuse, now off the consensus thread: the
    // signable digest derives from the framed bytes we already hold.
    message_signable_from_payload(payload, *msg, in.signable);
    in.has_signable = true;
  }
  in.msg = std::move(*msg);
  owner_->push_inbound(idx_, std::move(in));
}

void CryptoPipeline::seal_and_ship(int64_t dest, const std::string& payload) {
  if (chaos_drop_pct > 0 &&
      std::uniform_real_distribution<double>()(rng_) < chaos_drop_pct) {
    chaos_dropped.fetch_add(1, std::memory_order_relaxed);
    return;
  }
  PeerState& p = peers_[dest];
  std::string framed;
  if (p.chan && !p.chan->auth_only()) {
    // Bounded-outbound admission BEFORE the seal: sealing consumes the
    // link's AEAD nonce, so the drop must look like the frame was never
    // sealed (net.cc send_encoded's invariant, held across the offload).
    if (p.out_gauge &&
        (size_t)p.out_gauge->load(std::memory_order_relaxed) >
            max_conn_outbound()) {
      drops.fetch_add(1, std::memory_order_relaxed);
      return;
    }
    framed = frame_payload(p.chan->seal_frame(payload));
    if (!chaos_pass(dest, framed)) return;
  } else {
    framed = frame_payload(payload);
    if (!chaos_pass(dest, framed)) return;
    if (p.out_gauge &&
        (size_t)p.out_gauge->load(std::memory_order_relaxed) >
            max_conn_outbound()) {
      drops.fetch_add(1, std::memory_order_relaxed);
      return;
    }
  }
  LoopCmd w;
  w.kind = LoopCmd::kWritePeer;
  w.dest = dest;
  w.bytes = std::move(framed);
  // Forced: a post-seal drop here would desync the AEAD nonce sequence;
  // memory stays bounded by the pre-seal admission gate above.
  owner_->shard(idx_).push(std::move(w), /*force=*/true);
}

bool CryptoPipeline::chaos_pass(int64_t dest, const std::string& framed) {
  if (chaos_delay_ms <= 0) return true;
  int jitter = (int)(std::uniform_real_distribution<double>()(rng_) *
                     (double)chaos_delay_ms);
  chaos_queue_[dest].push_back(
      {std::chrono::steady_clock::now() + std::chrono::milliseconds(jitter),
       framed});
  return false;
}

void CryptoPipeline::pump_chaos(std::chrono::steady_clock::time_point now) {
  if (chaos_queue_.empty()) return;
  for (auto it = chaos_queue_.begin(); it != chaos_queue_.end();) {
    auto& dq = it->second;
    while (!dq.empty() && dq.front().first <= now) {
      // Per-destination FIFO release (sealed at admission): forced ship,
      // same reasoning as the seal path.
      LoopCmd w;
      w.kind = LoopCmd::kWritePeer;
      w.dest = it->first;
      w.bytes = std::move(dq.front().second);
      owner_->shard(idx_).push(std::move(w), /*force=*/true);
      dq.pop_front();
    }
    it = dq.empty() ? chaos_queue_.erase(it) : std::next(it);
  }
}

// -- NetShard ----------------------------------------------------------------

NetShard::~NetShard() {
  if (listen_fd_ >= 0) close(listen_fd_);
  for (auto& c : conns_)
    if (c->fd >= 0) close(c->fd);
  for (auto& [_, c] : peers_)
    if (c->fd >= 0) close(c->fd);
  for (auto& c : graveyard_)
    if (c->fd >= 0) close(c->fd);
}

bool NetShard::bind_listener(int port, bool reuseport, int* bound_port) {
  poller_ = make_poller();
  if (!wake_.open_fds()) return false;
  poller_->add(wake_.fd(), kShardTagWake, /*edge=*/false);
  listen_fd_ = socket(AF_INET, SOCK_STREAM, 0);
  if (listen_fd_ < 0) return false;
  tune_listen_socket(listen_fd_);
#ifdef SO_REUSEPORT
  if (reuseport) {
    int one = 1;
    setsockopt(listen_fd_, SOL_SOCKET, SO_REUSEPORT, &one, sizeof(one));
  }
#else
  (void)reuseport;
#endif
  sockaddr_in addr{};
  addr.sin_family = AF_INET;
  addr.sin_addr.s_addr = htonl(INADDR_ANY);
  addr.sin_port = htons((uint16_t)port);
  if (bind(listen_fd_, (sockaddr*)&addr, sizeof(addr)) != 0 ||
      listen(listen_fd_, 128) != 0) {
    // A non-SO_REUSEPORT host refuses the second bind: this shard runs
    // without a listener (dialed links + cmds only; shard 0 accepts all).
    close(listen_fd_);
    listen_fd_ = -1;
    if (idx_ == 0) return false;
    *bound_port = port;
    return true;
  }
  socklen_t len = sizeof(addr);
  getsockname(listen_fd_, (sockaddr*)&addr, &len);
  *bound_port = ntohs(addr.sin_port);
  shard_set_nonblocking(listen_fd_);
  poller_->add(listen_fd_, kShardTagListener, /*edge=*/false);
  return true;
}

void NetShard::push(LoopCmd&& c, bool force) {
  if (!cmds_.push(std::move(c), force)) {
    // The one command that is not forced is a reply's dial-back.
    backpressure.fetch_add(1, std::memory_order_relaxed);
    replies_dropped.fetch_add(1, std::memory_order_relaxed);
    return;
  }
  wake_.wake();
}

void NetShard::run() {
  LoopClock& ck = clock.clock;
  while (!owner_->stopping()) {
    ck.set_on(owner_->clocks_on());
    int timeout_ms = connecting_count_ > 0 ? 50 : 100;
    events_.clear();
    int n;
    {
      LoopClock::Scope waiting(ck, kLoopWait);
      n = poller_->wait(&events_, timeout_ms);
    }
    clock.publish();  // the wait has just been charged: no clock read here
    if (n < 0) continue;
    wakeups.fetch_add(1, std::memory_order_relaxed);
    for (const PollerEvent& ev : events_) {
      if (ev.tag == kShardTagListener) {
        if (ev.readable) {
          LoopClock::Scope reading(ck, kLoopRead);
          accept_ready();
        }
        continue;
      }
      if (ev.tag == kShardTagWake) {
        wake_.drain();
        continue;
      }
      Conn* c = reinterpret_cast<Conn*>((uintptr_t)ev.tag);
      if (c->closed) continue;
      if (c->connecting) {
        if (ev.writable || ev.error) finish_connect(*c);
        continue;
      }
      if (ev.readable || ev.error) handle_readable(*c);
      if (ev.writable && !c->closed) flush(*c);
    }
    process_cmds();
    pump_reply_backlog();
    sweep();
  }
}

void NetShard::process_cmds() {
  cmds_.drain(&local_);
  LoopClock& ck = clock.clock;
  // A connection is flushed once for all the frames this drained stretch
  // holds for it, not once a frame (ISSUE 41): a write queues and puts the
  // conn on touched_, the flush follows the stretch. A close finds the
  // frames written before it in the stretch already sent.
  auto write = [&](Conn& conn, const std::string& framed) {
    queue_bytes(conn, framed);
    if (!conn.touched) {
      conn.touched = true;
      touched_.push_back(&conn);
    }
  };
  auto close_conn = [&](Conn& conn) {
    if (conn.touched) {
      ck.enter(kLoopSend);
      flush(conn);  // may close it: close_when_flushed
      ck.enter(kLoopOther);
    }
    if (!conn.closed) mark_closed(conn);
  };
  for (LoopCmd& c : local_) {
    // As in the pipeline: a run of writes is one stretch of `send`.
    const bool writes =
        c.kind == LoopCmd::kWriteConn || c.kind == LoopCmd::kWritePeer;
    ck.enter(writes ? kLoopSend : kLoopOther);
    switch (c.kind) {
      case LoopCmd::kWriteConn: {
        auto it = by_token_.find(c.conn_id);
        if (it == by_token_.end() || it->second->closed) break;
        write(*it->second, c.bytes);
        break;
      }
      case LoopCmd::kWritePeer: {
        auto it = peers_.find(c.dest);
        if (it == peers_.end() || it->second->closed) break;  // loss is ok
        write(*it->second, c.bytes);
        break;
      }
      case LoopCmd::kDialPeer:
        dial_peer(c.dest, c.addr);
        break;
      case LoopCmd::kDialReply:
        start_reply_dial(c.addr, std::move(c.bytes));
        break;
      case LoopCmd::kCloseConn: {
        if (c.dest >= 0) {
          auto it = peers_.find(c.dest);
          if (it != peers_.end() && !it->second->closed) {
            close_conn(*it->second);
          }
          break;
        }
        auto it = by_token_.find(c.conn_id);
        if (it != by_token_.end() && !it->second->closed) {
          close_conn(*it->second);
        }
        break;
      }
    }
  }
  ck.enter(kLoopSend);
  flush_touched();
  ck.enter(kLoopOther);
  local_.clear();
}

// A Conn lives until sweep() (a closed peer link that was redialed waits
// in graveyard_), so the pointers hold; one that closed since it was
// touched is passed over.
void NetShard::flush_touched() {
  for (Conn* c : touched_) {
    c->touched = false;
    if (!c->closed) flush(*c);
  }
  touched_.clear();
}

void NetShard::accept_ready() {
  for (;;) {
    int fd = accept(listen_fd_, nullptr, nullptr);
    if (fd < 0) return;
    shard_set_nonblocking(fd);
    tune_stream_socket(fd);
    auto c = std::make_unique<Conn>();
    c->fd = fd;
    c->rbuf.data = pool_.acquire();
    c->shard_token = ++conn_seq_;
    c->out_gauge = std::make_shared<std::atomic<int64_t>>(0);
    register_conn(*c);
    by_token_[c->shard_token] = c.get();
    conns_.push_back(std::move(c));
  }
}

void NetShard::register_conn(Conn& c) {
  poller_->add(c.fd, (uint64_t)(uintptr_t)&c, /*edge=*/true);
  if (c.connecting || !c.out.empty()) {
    poller_->set_write_interest(c.fd, true);
  }
}

void NetShard::handle_readable(Conn& c) {
  LoopClock::Scope reading(clock.clock, kLoopRead);
  char buf[65536];
  for (;;) {
    ssize_t r = read(c.fd, buf, sizeof(buf));
    if (r > 0) {
      c.rbuf.append(buf, (size_t)r);
      continue;
    }
    if (r < 0 && (errno == EAGAIN || errno == EWOULDBLOCK)) break;
    if (!c.rbuf.empty()) process_buffer(c);
    mark_closed(c);
    return;
  }
  process_buffer(c);
}

void NetShard::process_buffer(Conn& c) {
  if (c.close_when_flushed) {
    c.rbuf.reset();  // untrusted dial-back endpoint: never parse
    return;
  }
  if (!c.sniffed && !c.rbuf.empty()) {
    c.sniffed = true;
    c.raw_json = c.rbuf.at(0) == '{';
  }
  if (c.raw_json) {
    // Line framing stays here (cheap scan); JSON parsing moves to the
    // pipeline. The eager whole-buffer parse for no-newline senders is
    // the one exception — rare (telnet paste), bounded at 1 MiB.
    for (;;) {
      auto nl = c.rbuf.find('\n');
      std::string payload;
      if (nl != std::string::npos) {
        payload = c.rbuf.take(nl);
        c.rbuf.consume(1);
      } else if (c.closed || c.fd < 0) {
        payload = c.rbuf.take(c.rbuf.size());
      } else {
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
      CryptoCmd cmd;
      cmd.kind = CryptoCmd::kInboundLine;
      cmd.conn_id = c.shard_token;
      cmd.bytes = std::move(payload);
      owner_->pipeline(idx_).push(std::move(cmd), /*force=*/false);
      if (c.rbuf.empty()) return;
    }
  }
  for (;;) {
    if (c.rbuf.size() < 4) return;
    uint32_t len = ((uint32_t)c.rbuf.at(0) << 24) |
                   ((uint32_t)c.rbuf.at(1) << 16) |
                   ((uint32_t)c.rbuf.at(2) << 8) | (uint32_t)c.rbuf.at(3);
    if (len > (1u << 24)) {
      mark_closed(c);
      return;
    }
    if (c.rbuf.size() < 4 + (size_t)len) return;
    c.rbuf.consume(4);
    std::string payload = c.rbuf.take(len);
    if (c.offloaded) {
      CryptoCmd cmd;
      cmd.kind = CryptoCmd::kInboundFrame;
      cmd.conn_id = c.peer_dest >= 0 ? 0 : c.shard_token;
      cmd.dest = c.peer_dest;
      cmd.bytes = std::move(payload);
      owner_->pipeline(idx_).push(std::move(cmd), /*force=*/false);
      continue;
    }
    if (!handle_prologue_frame(c, std::move(payload))) return;
  }
}

bool NetShard::reject_conn(Conn& c, const std::string& reason) {
  std::fprintf(stderr, "replica %lld shard %d: rejecting peer link: %s\n",
               (long long)owner_->id(), idx_, reason.c_str());
  queue_bytes(c, frame_payload(SecureChannel::reject_payload(reason)));
  flush(c);
  if (!c.closed) mark_closed(c);
  return false;
}

// Hand an established link's crypto state to the pipeline: from here on
// the loop thread only moves bytes for this conn.
void NetShard::offload_established(Conn& c, int64_t dest) {
  c.offloaded = true;
  CryptoCmd cmd;
  cmd.kind = CryptoCmd::kConnEstablished;
  cmd.conn_id = dest >= 0 ? 0 : c.shard_token;
  cmd.dest = dest;
  cmd.chan = std::move(c.chan);
  cmd.codec_binary = c.codec_binary;
  cmd.mac = c.mac_ready;
  cmd.gateway = c.gateway;
  cmd.out_gauge = c.out_gauge;
  owner_->pipeline(idx_).push(std::move(cmd), /*force=*/true);
}

// The link prologue (version hello, gateway trust, signed-DH handshake)
// stays on the loop thread — once per connection, never hot. Mirrors
// net.cc handle_peer_frame's pre-established branches.
bool NetShard::handle_prologue_frame(Conn& c, std::string payload) {
  const ClusterConfig& cfg = owner_->cfg();
  if (c.peer_dest >= 0) {
    if (c.chan && !c.chan->established()) {
      auto j = Json::parse(payload);
      if (!j) {
        mark_closed(c);
        return false;
      }
      if (c.chan->auth_only()) {
        // Authenticator mode on a plaintext cluster: an old (or
        // signature-mode) responder answers with a classic hello-ack —
        // downgrade this link to the plain flavor (net.cc mirror).
        const Json* t = j->find("type");
        if (t && t->is_string() && t->as_string() == "reject") {
          mark_closed(c);
          return false;
        }
        const Json* eph = j->find("eph");
        if (!eph || !eph->is_string()) {
          c.chan.reset();
          if (t && t->is_string() && t->as_string() == "hello") {
            c.codec_binary = hello_offers_binary(*j);
          }
          offload_established(c, c.peer_dest);
          return true;
        }
      }
      auto auth = c.chan->on_hello_reply(*j);
      if (!auth) {
        mark_closed(c);
        return false;
      }
      c.codec_binary = hello_offers_binary(*j);
      if (c.chan->mac_negotiated()) {
        // Register the sender-side lane key in the cross-shard table
        // BEFORE the channel moves to the pipeline (this thread still
        // owns it; broadcasts from any pipeline read the table).
        c.mac_ready = true;
        owner_->set_mac_key(c.peer_dest, c.chan->auth_send_key());
      }
      queue_bytes(c, frame_payload(*auth));
      flush(c);
      if (c.closed) return false;
      offload_established(c, c.peer_dest);
      return true;
    }
    if (!c.chan && !c.offloaded) {
      auto j = Json::parse(payload);
      const Json* t = j ? j->find("type") : nullptr;
      if (t && t->is_string() && t->as_string() == "reject") {
        mark_closed(c);
        return false;
      }
      if (t && t->is_string() && t->as_string() == "hello") {
        // Plaintext hello-ack: codec negotiated, link ready. Payloads
        // held in the pipeline's pending queue go out now (the
        // single-loop runtime sends pre-ack frames as JSON immediately;
        // here they wait for the ack — one RTT on a fresh link, and the
        // codec choice can only improve).
        c.codec_binary = hello_offers_binary(*j);
        offload_established(c, c.peer_dest);
      }
      return true;
    }
    return true;
  }
  if (!c.hello_seen) {
    auto j = Json::parse(payload);
    const Json* t = j ? j->find("type") : nullptr;
    bool is_hello = t && t->is_string() && t->as_string() == "hello";
    if (is_hello) {
      std::string err;
      if (!SecureChannel::check_version(*j, &err)) return reject_conn(c, err);
      c.hello_seen = true;
      c.peer_mac = owner_->fastpath_mac() && hello_offers_mac(*j);
      const Json* role = j->find("role");
      if (role && role->is_string() && role->as_string() == "gateway") {
        if (cfg.secure) {
          return reject_conn(
              c, "gateway links require a plaintext cluster (a gateway "
                 "has no replica identity to authenticate)");
        }
        c.gateway = true;
      }
      const Json* eph = j->find("eph");
      if (cfg.secure) {
        c.chan = std::make_unique<SecureChannel>(&cfg, owner_->id(),
                                                 owner_->seed(),
                                                 /*initiator=*/false,
                                                 /*expected_peer=*/-1,
                                                 owner_->fastpath_mac());
        auto reply = c.chan->on_hello(*j);
        if (!reply) return reject_conn(c, c.chan->error());
        queue_bytes(c, frame_payload(*reply));
        flush(c);
        return !c.closed;
      }
      if (c.peer_mac && eph && eph->is_string()) {
        // Authenticator mode on a plaintext cluster (ISSUE 14): the
        // SAME signed handshake, auth-only — frames stay plaintext.
        c.chan = std::make_unique<SecureChannel>(&cfg, owner_->id(),
                                                 owner_->seed(),
                                                 /*initiator=*/false,
                                                 /*expected_peer=*/-1,
                                                 owner_->fastpath_mac(),
                                                 /*auth_only=*/true);
        auto reply = c.chan->on_hello(*j);
        if (!reply) return reject_conn(c, c.chan->error());
        queue_bytes(c, frame_payload(*reply));
        flush(c);
        return !c.closed;
      }
      queue_bytes(c, frame_payload(SecureChannel::plain_hello(
                         owner_->id(), owner_->fastpath_mac())));
      flush(c);
      if (c.closed) return false;
      offload_established(c, -1);
      return true;
    }
    if (cfg.secure) {
      return reject_conn(
          c, "plaintext peer rejected: first frame must be an "
             "encrypted-link hello");
    }
    c.hello_seen = true;  // tooling compat: framed protocol, no hello
    offload_established(c, -1);
    CryptoCmd cmd;  // this first frame is already protocol payload
    cmd.kind = CryptoCmd::kInboundFrame;
    cmd.conn_id = c.shard_token;
    cmd.bytes = std::move(payload);
    owner_->pipeline(idx_).push(std::move(cmd), /*force=*/false);
    return true;
  }
  if (c.chan && !c.chan->established()) {
    auto j = Json::parse(payload);
    if (!j || !c.chan->on_auth(*j)) {
      return reject_conn(c, c.chan->error().empty() ? "malformed auth frame"
                                                    : c.chan->error());
    }
    if (c.chan->mac_negotiated()) c.mac_ready = true;
    offload_established(c, -1);
    return true;
  }
  return true;
}

void NetShard::queue_bytes(Conn& c, const std::string& framed) {
  auto& q = c.out;
  if (!q.blocks.empty() &&
      q.blocks.back().size() + framed.size() <= max_send_block()) {
    q.blocks.back() += framed;
  } else {
    std::string b = pool_.acquire();
    b += framed;
    q.blocks.push_back(std::move(b));
  }
  q.bytes += framed.size();
  if (c.out_gauge) {
    c.out_gauge->store((int64_t)q.bytes, std::memory_order_relaxed);
  }
  frames_out.store(frames_out.load(std::memory_order_relaxed) + 1,
                   std::memory_order_relaxed);
}

void NetShard::flush(Conn& c) {
  if (c.connecting) return;
  LoopClock::Scope sending(clock.clock, kLoopSend);
  SendQueue& q = c.out;
  while (!q.blocks.empty()) {
    std::string& b = q.blocks.front();
    size_t avail = b.size() - q.front_pos;
    if (avail == 0) {
      pool_.release(std::move(b));
      q.blocks.pop_front();
      q.front_pos = 0;
      continue;
    }
    ssize_t w = send(c.fd, b.data() + q.front_pos, avail, MSG_NOSIGNAL);
    send_calls.store(send_calls.load(std::memory_order_relaxed) + 1,
                     std::memory_order_relaxed);
    if (w > 0) {
      q.front_pos += (size_t)w;
      q.bytes -= (size_t)w;
      continue;
    }
    if (w < 0 && (errno == EAGAIN || errno == EWOULDBLOCK)) {
      poller_->set_write_interest(c.fd, true);
      if (!c.backpressured) {
        c.backpressured = true;
        backpressure.fetch_add(1, std::memory_order_relaxed);
      }
      if (c.out_gauge) {
        c.out_gauge->store((int64_t)q.bytes, std::memory_order_relaxed);
      }
      return;
    }
    mark_closed(c);
    return;
  }
  q.front_pos = 0;
  c.backpressured = false;
  if (c.out_gauge) c.out_gauge->store(0, std::memory_order_relaxed);
  poller_->set_write_interest(c.fd, false);
  if (c.close_when_flushed) mark_closed(c);
}

void NetShard::mark_closed(Conn& c) {
  if (c.closed) return;
  // A dialed mac link's lane key dies with the connection.
  if (c.peer_dest >= 0 && c.mac_ready) {
    owner_->erase_mac_key(c.peer_dest);
  }
  if (c.fd >= 0) {
    poller_->remove(c.fd);
    close(c.fd);
  }
  c.closed = true;
  pool_.release(std::move(c.rbuf.data));
  c.rbuf = RecvBuf{};
  for (auto& b : c.out.blocks) pool_.release(std::move(b));
  c.out = SendQueue{};
  if (c.out_gauge) c.out_gauge->store(0, std::memory_order_relaxed);
  if (c.shard_token != 0) by_token_.erase(c.shard_token);
  if (c.offloaded || c.peer_dest >= 0) {
    CryptoCmd cmd;
    cmd.kind = CryptoCmd::kConnClosed;
    cmd.conn_id = c.peer_dest >= 0 ? 0 : c.shard_token;
    cmd.dest = c.peer_dest;
    owner_->pipeline(idx_).push(std::move(cmd), /*force=*/true);
  }
  if (c.close_when_flushed) {
    if (reply_dials_in_flight_ > 0) --reply_dials_in_flight_;
    if (!c.reply_addr.empty()) reply_addrs_in_flight_.erase(c.reply_addr);
  }
}

void NetShard::finish_connect(Conn& c) {
  int err = 0;
  socklen_t len = sizeof(err);
  if (getsockopt(c.fd, SOL_SOCKET, SO_ERROR, &err, &len) != 0 || err != 0) {
    mark_closed(c);
    return;
  }
  c.connecting = false;
  flush(c);
}

void NetShard::dial_peer(int64_t dest, const std::string& addr) {
  auto it = peers_.find(dest);
  if (it != peers_.end()) {
    if (!it->second->closed) return;  // live (or still connecting)
    // Closed but unswept: park the object until the end-of-pass sweep (a
    // stale event this pass may still reference it) and free the slot so
    // the redial isn't deferred a full pass.
    graveyard_.push_back(std::move(it->second));
    peers_.erase(it);
  }
  bool in_progress = false;
  int fd = dial_tcp_nb(addr, &in_progress);
  if (fd < 0) return;
  auto c = std::make_unique<Conn>();
  c->fd = fd;
  c->peer_dest = dest;
  c->connecting = in_progress;
  c->connect_deadline =
      std::chrono::steady_clock::now() + std::chrono::seconds(10);
  c->rbuf.data = pool_.acquire();
  c->out_gauge = std::make_shared<std::atomic<int64_t>>(0);
  const ClusterConfig& cfg = owner_->cfg();
  if (cfg.secure || owner_->fastpath_mac()) {
    // Authenticator mode on a plaintext cluster runs the SAME signed
    // handshake auth-only (lane keys + identity; frames stay
    // plaintext); an old responder downgrades in the prologue.
    c->chan = std::make_unique<SecureChannel>(
        &cfg, owner_->id(), owner_->seed(),
        /*initiator=*/true, dest, owner_->fastpath_mac(),
        /*auth_only=*/!cfg.secure);
    queue_bytes(*c, frame_payload(c->chan->initiator_hello()));
  } else {
    queue_bytes(*c, frame_payload(SecureChannel::plain_hello(owner_->id())));
  }
  register_conn(*c);
  peers_[dest] = std::move(c);
}

void NetShard::start_reply_dial(const std::string& addr,
                                std::string payload) {
  if (reply_dials_in_flight_ < kShardMaxReplyDials &&
      !reply_addrs_in_flight_.count(addr)) {
    reply_dial_now(addr, std::move(payload));
  } else if (reply_backlog_.size() < kShardMaxReplyBacklog) {
    reply_backlog_.push_back(QueuedReply{addr, std::move(payload),
                                         std::chrono::steady_clock::now()});
  } else {
    replies_dropped.fetch_add(1, std::memory_order_relaxed);
  }
}

void NetShard::reply_dial_now(const std::string& addr, std::string payload) {
  bool in_progress = false;
  int fd = dial_tcp_nb(addr, &in_progress);
  if (fd < 0) return;
  auto c = std::make_unique<Conn>();
  c->fd = fd;
  c->connecting = in_progress;
  c->connect_deadline =
      std::chrono::steady_clock::now() + std::chrono::seconds(3);
  c->close_when_flushed = true;
  c->reply_addr = addr;
  c->rbuf.data = pool_.acquire();
  c->shard_token = ++conn_seq_;
  queue_bytes(*c, payload);
  ++reply_dials_in_flight_;
  reply_addrs_in_flight_.insert(addr);
  register_conn(*c);
  flush(*c);
  if (!c->closed) {
    by_token_[c->shard_token] = c.get();
    conns_.push_back(std::move(c));
  }
}

void NetShard::pump_reply_backlog() {
  auto now = std::chrono::steady_clock::now();
  std::deque<QueuedReply> keep;
  while (!reply_backlog_.empty()) {
    auto entry = std::move(reply_backlog_.front());
    reply_backlog_.pop_front();
    if (now - entry.enqueued > kShardReplyBacklogTtl) {
      replies_dropped.fetch_add(1, std::memory_order_relaxed);
      continue;
    }
    if (reply_dials_in_flight_ >= kShardMaxReplyDials) {
      keep.push_back(std::move(entry));
      while (!reply_backlog_.empty()) {
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

// Per-shard sweep (ISSUE 13 satellite): each shard reaps ITS overdue
// nonblocking connects and closed conns — the bookkeeping that was
// single-loop global state in net.cc is shard-local here.
void NetShard::sweep() {
  const auto now = std::chrono::steady_clock::now();
  connecting_count_ = 0;
  auto visit = [&](Conn& c) {
    if (!c.closed && c.connecting) {
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
    it = it->second->closed ? peers_.erase(it) : std::next(it);
  }
  graveyard_.clear();
  conns_open.store((int64_t)(conns_.size() + peers_.size()),
                   std::memory_order_relaxed);
}

// -- NetShards ---------------------------------------------------------------

NetShards::NetShards(const ClusterConfig& cfg, int64_t id,
                     const uint8_t seed[32], std::atomic<bool>* stopping,
                     int nshards)
    : cfg_(cfg), id_(id), stopping_(stopping) {
  std::memcpy(seed_, seed, 32);
  fastpath_mac_ = wire_offer_mac(cfg_.fastpath == "mac");
  nshards = std::max(1, nshards);
  for (int i = 0; i < nshards; ++i) {
    shards_.push_back(std::make_unique<NetShard>(this, i));
    pipelines_.push_back(std::make_unique<CryptoPipeline>(this, i));
    inbox_.push_back(std::make_unique<CmdQueue<KInbound>>(65536));
  }
}

NetShards::~NetShards() { stop_join(); }

void NetShards::set_chaos(double drop_pct, int delay_ms, uint64_t seed) {
  for (size_t i = 0; i < pipelines_.size(); ++i) {
    pipelines_[i]->chaos_drop_pct = drop_pct;
    pipelines_[i]->chaos_delay_ms = delay_ms;
    // Per-shard streams stay deterministic for a given (seed, shard):
    // the golden-ratio odd multiplier decorrelates them.
    pipelines_[i]->chaos_seed = seed + 0x9E3779B97F4A7C15ull * (i + 1);
  }
}

bool NetShards::start(int* listen_port_out) {
  if (!k_wake_.open_fds()) return false;
  int port = cfg_.replicas[id_].port;
  int bound = 0;
  if (!shards_[0]->bind_listener(port, /*reuseport=*/true, &bound)) {
    return false;
  }
  for (size_t i = 1; i < shards_.size(); ++i) {
    int tmp = 0;
    if (!shards_[i]->bind_listener(bound, /*reuseport=*/true, &tmp)) {
      return false;
    }
  }
  *listen_port_out = bound;
  for (auto& s : shards_) {
    threads_.emplace_back([sp = s.get()] { sp->run(); });
  }
  for (auto& p : pipelines_) {
    threads_.emplace_back([pp = p.get()] { pp->run(); });
  }
  started_ = true;
  return true;
}

void NetShards::stop_join() {
  if (!started_ || joined_) return;
  stopping_->store(true, std::memory_order_relaxed);
  for (auto& s : shards_) s->wake_.wake();
  for (auto& p : pipelines_) p->notify();
  for (auto& t : threads_) {
    if (t.joinable()) t.join();
  }
  joined_ = true;
}

void NetShards::drain_inbox(std::deque<KInbound>* out,
                            CmdQueue<KInbound>::Stamp* oldest) {
  k_wake_.drain();
  for (auto& q : inbox_) q->drain(out, oldest);
}

void NetShards::push_inbound(int shard, KInbound&& in) {
  const bool control = in.kind != KInbound::kMsg;
  if (!inbox_[shard]->push(std::move(in), control, /*stamp=*/clocks_on())) {
    inbox_dropped_.fetch_add(1, std::memory_order_relaxed);
    return;
  }
  k_wake_.wake();
}

void NetShards::send_peer(int64_t dest, const std::string& addr,
                          const std::shared_ptr<ShardEncoded>& enc) {
  CryptoCmd c;
  c.kind = CryptoCmd::kSendPeer;
  c.dest = dest;
  c.addr = addr;
  c.enc = enc;
  pipelines_[shard_of(dest)]->push(std::move(c), /*force=*/false);
}

void NetShards::send_gateway_line(int shard, uint64_t conn_id,
                                  std::string line) {
  CryptoCmd c;
  c.kind = CryptoCmd::kSendClientLine;
  c.conn_id = conn_id;
  c.bytes = std::move(line);
  pipelines_[shard]->push(std::move(c), /*force=*/false);
}

void NetShards::dial_reply(const std::string& addr, std::string payload) {
  LoopCmd d;
  d.kind = LoopCmd::kDialReply;
  d.addr = addr;
  d.bytes = std::move(payload);
  int si = (int)(std::hash<std::string>{}(addr) % shards_.size());
  shards_[si]->push(std::move(d), /*force=*/false);
}

int64_t NetShards::shard_wakeups(int i) const {
  return shards_[i]->wakeups.load(std::memory_order_relaxed);
}

int64_t NetShards::total_wakeups() const {
  int64_t t = 0;
  for (auto& s : shards_) t += s->wakeups.load(std::memory_order_relaxed);
  return t;
}

int64_t NetShards::cross_thread_wakes() const {
  int64_t t = k_wake_.wakes();
  for (auto& s : shards_) t += s->wake_.wakes();
  return t;
}

int64_t NetShards::connections_open() const {
  int64_t t = 0;
  for (auto& s : shards_) t += s->conns_open.load(std::memory_order_relaxed);
  return t;
}

int64_t NetShards::crypto_queue_depth() const {
  int64_t t = 0;
  for (auto& p : pipelines_) {
    t += p->queue_depth.load(std::memory_order_relaxed);
  }
  return t;
}

int64_t NetShards::mac_frames() const {
  int64_t t = 0;
  for (auto& p : pipelines_) {
    t += p->mac_frames.load(std::memory_order_relaxed);
  }
  return t;
}

int64_t NetShards::frames_out() const {
  int64_t t = 0;
  for (auto& s : shards_) t += s->frames_out.load(std::memory_order_relaxed);
  return t;
}

int64_t NetShards::send_calls() const {
  int64_t t = 0;
  for (auto& s : shards_) t += s->send_calls.load(std::memory_order_relaxed);
  return t;
}

int64_t NetShards::mac_rejected() const {
  int64_t t = 0;
  for (auto& p : pipelines_) {
    t += p->mac_rejected.load(std::memory_order_relaxed);
  }
  return t;
}

void NetShards::set_mac_key(int64_t dest, const uint8_t key[32]) {
  std::array<uint8_t, 32> k;
  std::memcpy(k.data(), key, 32);
  std::lock_guard<std::mutex> lk(mac_mu_);
  mac_send_keys_[dest] = k;
}

void NetShards::erase_mac_key(int64_t dest) {
  std::lock_guard<std::mutex> lk(mac_mu_);
  mac_send_keys_.erase(dest);
}

std::map<int64_t, std::array<uint8_t, 32>> NetShards::mac_key_snapshot()
    const {
  std::lock_guard<std::mutex> lk(mac_mu_);
  return mac_send_keys_;
}

int64_t NetShards::backpressure_events() const {
  int64_t t = inbox_dropped_.load(std::memory_order_relaxed);
  for (auto& s : shards_) t += s->backpressure.load(std::memory_order_relaxed);
  for (auto& p : pipelines_) t += p->drops.load(std::memory_order_relaxed);
  return t;
}

int64_t NetShards::pipeline_dropped() const {
  int64_t t = 0;
  for (auto& p : pipelines_) t += p->drops.load(std::memory_order_relaxed);
  return t;
}

int64_t NetShards::replies_dropped() const {
  int64_t t = 0;
  for (auto& s : shards_) {
    t += s->replies_dropped.load(std::memory_order_relaxed);
  }
  return t;
}

int64_t NetShards::chaos_dropped() const {
  int64_t t = 0;
  for (auto& p : pipelines_) {
    t += p->chaos_dropped.load(std::memory_order_relaxed);
  }
  return t;
}

}  // namespace pbft
