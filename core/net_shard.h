// Multi-core replica front end (ISSUE 13): shard connections across N
// event-loop threads and move AEAD seal/open + payload codec work off the
// loop threads into per-shard crypto pipelines, while the protocol state
// machine (Replica) stays owned by ONE consensus thread.
//
// Thread/ownership model (net_threads = N > 1):
//
//   loop shard i  (NetShard, thread)    — SO_REUSEPORT listener on the
//       replica port, a persistent-registration Poller, and every socket
//       it accepted plus the dialed peer links for dest % N == i. Does
//       framing (length prefix / raw-JSON lines) and the link prologue
//       (version hello, signed-DH handshake) — the rare per-connection
//       setup — then hands the established SecureChannel to its pipeline.
//   crypto pipeline i (CryptoPipeline, thread) — AEAD open/seal, binary-v2
//       / JSON payload decode+encode, and the per-shard chaos bookkeeping,
//       for shard i's connections ONLY. One pipeline thread per shard and
//       strictly FIFO command processing is what preserves the secure-link
//       nonce order invariant: a connection's frames are sealed (and
//       opened) in exactly the order they were enqueued.
//   consensus thread (ReplicaServer::poll_once) — owns Replica, the verify
//       windows, all timers, tracing, and the metrics registry. Parsed
//       messages arrive over bounded per-shard SPSC queues; an eventfd
//       (pipe fallback) wake makes the handoff visible to its poller.
//
// Everything crossing a thread boundary goes through one of the bounded
// queues below; data frames drop-and-count on overflow (PBFT
// retransmission absorbs the loss, exactly like a chaos link drop) while
// control messages (connection lifecycle) always enqueue. There is no
// shared mutable protocol state: cfg/seed are read-only after start, and
// the only non-queue sharing is per-connection relaxed atomics
// (outbound-bytes gauges, stats counters) and each front-end thread's
// published stage clock (FrontClock below). Order is kept a CONNECTION
// (one owner a socket, one pipeline a shard, FIFO queues), not across
// connections: two connections of one client may land on two shards.
#pragma once

#include <atomic>
#include <condition_variable>
#include <cstdint>
#include <deque>
#include <map>
#include <memory>
#include <mutex>
#include <optional>
#include <random>
#include <string>
#include <thread>
#include <vector>

#include "net.h"

namespace pbft {

// Cross-thread wake: eventfd on Linux, a nonblocking pipe elsewhere. The
// producer side is writable from any thread (and is async-signal-safe);
// the consumer registers fd() level-triggered with its poller and calls
// drain() before consuming its queues. drain() empties the fd FIRST and
// clears the coalescing flag SECOND (net_shard.cc has why the other order
// loses every later wake), so a push after the clear writes the fd afresh
// and a push before it is taken by the queue drain that follows: a wake
// can coalesce, never vanish. `wakes` feeds pbft_cross_thread_wakes_total.
class WakeFd {
 public:
  ~WakeFd();
  bool open_fds();
  int fd() const { return rfd_; }
  void wake();   // counted; coalesces while the consumer hasn't drained
  void drain();  // consumer: empty the fd, clear the flag, THEN drain queues
  int64_t wakes() const { return wakes_.load(std::memory_order_relaxed); }

 private:
  int rfd_ = -1;
  int wfd_ = -1;
  std::atomic<bool> signaled_{false};
  std::atomic<int64_t> wakes_{0};
};

// A broadcast payload shared across shard pipelines: canonical JSON and
// binary-v2 encodings are computed lazily, AT MOST ONCE each, whichever
// pipeline gets there first — the serialize-once invariant of EncodedOut,
// made thread-safe (the encode itself now runs OFF the consensus thread).
class ShardEncoded {
 public:
  ShardEncoded(Message m, std::atomic<int64_t>* encode_tally)
      : m_(std::move(m)), tally_(encode_tally) {}
  const std::string& json_payload();
  const std::string* binary_payload();  // nullptr: no binary form
  // MAC-vector variant (ISSUE 14): lanes over the owner's shared key
  // table (one lane per mac-negotiated peer link, whichever shard owns
  // it), computed at most once — the serialize-once invariant extended
  // to the authenticator mode across shards. nullptr: no MAC form.
  const std::string* mac_payload(NetShards* owner);

 private:
  Message m_;
  std::atomic<int64_t>* tally_;
  std::mutex mu_;
  std::string json_, binary_, mac_;
  bool json_done_ = false;
  bool bin_tried_ = false;
  bool bin_ok_ = false;
  bool mac_tried_ = false;
  bool mac_ok_ = false;
};

// Bounded cross-thread command queue: mutex + deque, drained by swap so
// the consumer holds the lock O(1) per pass. `force` bypasses the bound
// for control messages whose loss would wedge a connection's lifecycle.
template <typename T>
class CmdQueue {
 public:
  using Stamp = std::chrono::steady_clock::time_point;
  explicit CmdQueue(size_t cap) : cap_(cap) {}
  // `stamp`: a push that finds the queue empty notes the instant, which
  // the drain that takes it hands back as the age of its OLDEST entry
  // (pbft_shard_handoff_seconds): one clock read a drain's worth of
  // pushes, none a message.
  bool push(T&& v, bool force, bool stamp = false) {
    std::lock_guard<std::mutex> lk(mu_);
    if (!force && q_.size() >= cap_) return false;
    if (stamp && q_.empty()) oldest_ = std::chrono::steady_clock::now();
    q_.push_back(std::move(v));
    return true;
  }
  // `oldest` (optional) is lowered to this queue's stamp where it holds
  // a stamped entry.
  void drain(std::deque<T>* out, Stamp* oldest = nullptr) {
    std::lock_guard<std::mutex> lk(mu_);
    if (oldest && !q_.empty() && oldest_ != Stamp{}) {
      *oldest = std::min(*oldest, oldest_);
    }
    oldest_ = Stamp{};
    if (out->empty()) {
      out->swap(q_);
    } else {
      while (!q_.empty()) {
        out->push_back(std::move(q_.front()));
        q_.pop_front();
      }
    }
  }
  size_t size() const {
    std::lock_guard<std::mutex> lk(mu_);
    return q_.size();
  }

 private:
  mutable std::mutex mu_;
  std::deque<T> q_;
  size_t cap_;
  Stamp oldest_{};
};

// A front-end thread's stage clock (ISSUE 40): the class the consensus
// thread's loop has (net.h LoopClock: one steady_clock read where the
// stage changes, plain integers), one instance a shard thread and one a
// pipeline thread, owned by that thread alone. Of LoopClock's seven slots
// such a thread uses four; it copies them into relaxed atomics once a
// pass, where its wait has just ended (no clock read of its own), and the
// consensus thread reads those at a scrape. So a reading lags by what the
// thread has done since its last wait began, at most one wait's timeout.
// LoopClock slots behind the four (net.h kFrontStages), in the names' order.
inline constexpr int kFrontStageSlot[kFrontStages] = {kLoopWait, kLoopRead,
                                                      kLoopSend, kLoopOther};
// Shard: wait (the poller), read (recv, framing, the link prologue,
// accepts), send (queue_bytes, flush, the send() calls), other.
inline constexpr const char* kShardStageNames[kFrontStages] = {
    "wait", "read", "send", "other"};
// Pipeline: wait (the condition variable), decode (open, parse, signable,
// MAC check, the push to the consensus inbox), encode (encode, MAC tags,
// seal, frame, the push to the shard), other.
inline constexpr const char* kPipeStageNames[kFrontStages] = {
    "wait", "decode", "encode", "other"};
constexpr int kPipeDecode = kLoopRead;
constexpr int kPipeEncode = kLoopSend;

struct FrontClock {
  LoopClock clock;
  std::array<std::atomic<int64_t>, kFrontStages> ns{};
  void publish() {
    for (int i = 0; i < kFrontStages; ++i) {
      ns[i].store(clock.ns[kFrontStageSlot[i]], std::memory_order_relaxed);
    }
  }
  int64_t stage_ns(int i) const { return ns[i].load(std::memory_order_relaxed); }
};

// Consensus thread -> pipeline i, and loop shard i -> pipeline i.
struct CryptoCmd {
  enum Kind {
    kInboundFrame,     // framed payload off an established link (open+parse)
    kInboundLine,      // raw-JSON client line (parse)
    kConnEstablished,  // link prologue done: adopt crypto state for a conn
                       // (the hello-ack's codec offer rides along)
    kConnClosed,       // drop per-conn state; notify K for gateway links
    kSendPeer,         // protocol payload toward dest (encode+seal+frame)
    kSendClientLine,   // raw-JSON line back over a gateway link (frame)
    kDialReply,        // one-shot dial-back (pass-through to the shard)
  };
  Kind kind;
  uint64_t conn_id = 0;  // accepted-link token (0 = none)
  int64_t dest = -1;     // dialed peer link id (-1 = none)
  std::string bytes;     // payload / line / framed data
  std::string addr;      // dial target (kSendPeer first dial, kDialReply)
  std::shared_ptr<ShardEncoded> enc;          // kSendPeer
  std::unique_ptr<SecureChannel> chan;        // kConnEstablished (may be null)
  std::shared_ptr<std::atomic<int64_t>> out_gauge;  // conn outbound bytes
  bool codec_binary = false;
  bool mac = false;  // link negotiated the MAC authenticator (ISSUE 14)
  bool gateway = false;
};

// Pipeline i -> loop shard i.
struct LoopCmd {
  enum Kind {
    kWriteConn,   // framed bytes onto an accepted conn (gateway reply)
    kWritePeer,   // framed bytes onto the dialed link for dest
    kDialPeer,    // ensure a dialed link to dest exists (hello queued)
    kDialReply,   // one-shot raw-JSON dial-back toward a client address
    kCloseConn,   // AEAD failure upstream: drop the accepted conn
  };
  Kind kind;
  uint64_t conn_id = 0;
  int64_t dest = -1;
  std::string bytes;
  std::string addr;
};

// Pipeline i -> consensus thread.
struct KInbound {
  enum Kind { kMsg, kGatewayUp, kGatewayDown };
  Kind kind = kMsg;
  int shard = 0;
  uint64_t conn_id = 0;       // gateway-link token for routing replies back
  bool from_gateway = false;  // request arrived over a gateway link
  bool has_signable = false;
  // The pipeline verified this frame's MAC lane against its link's
  // session key (ISSUE 14): the consensus thread dispatches it without
  // the verify queue.
  bool pre_authenticated = false;
  uint8_t signable[32] = {0};
  std::optional<Message> msg;
};

class NetShards;

// One crypto pipeline thread (see the file comment for the model).
class CryptoPipeline {
 public:
  CryptoPipeline(NetShards* owner, int idx) : owner_(owner), idx_(idx) {}
  void push(CryptoCmd&& c, bool force);
  void notify();
  void run();  // thread body

  std::atomic<int64_t> queue_depth{0};  // pbft_crypto_offload_queue_depth
  std::atomic<int64_t> mac_frames{0};    // MAC-vector frames sent
  std::atomic<int64_t> mac_rejected{0};  // inbound lane mismatches
  std::atomic<int64_t> chaos_dropped{0};
  std::atomic<int64_t> drops{0};  // bounded-queue / admission drops
  FrontClock clock;               // pbft_pipe_<stage>_us_total

  // Per-shard chaos bookkeeping (ISSUE 13 satellite): the same knobs as
  // the single-loop runtime, seeded per shard so the stream stays
  // deterministic for a given (seed, shard) pair.
  double chaos_drop_pct = 0.0;
  int chaos_delay_ms = 0;
  uint64_t chaos_seed = 0xC4A05;

 private:
  friend class NetShards;
  void handle(CryptoCmd& c);
  void open_and_forward(uint64_t conn_id, int64_t dest, std::string payload);
  void parse_to_k(uint64_t conn_id, bool from_gateway, std::string payload,
                  SecureChannel* chan = nullptr);
  void seal_and_ship(int64_t dest, const std::string& payload);
  bool chaos_pass(int64_t dest, const std::string& framed);
  void pump_chaos(std::chrono::steady_clock::time_point now);

  struct PeerState {
    bool ready = false;  // link prologue done (chan set or plaintext)
    bool codec_binary = false;
    bool mac = false;  // link negotiated the MAC authenticator
    std::unique_ptr<SecureChannel> chan;  // null on plaintext links
    std::vector<std::string> pending;     // payloads queued pre-handshake
    std::shared_ptr<std::atomic<int64_t>> out_gauge;
  };
  struct ConnState {
    std::unique_ptr<SecureChannel> chan;  // null on plaintext links
    bool mac = false;
    bool gateway = false;
    std::shared_ptr<std::atomic<int64_t>> out_gauge;
  };

  NetShards* owner_;
  int idx_;
  std::map<int64_t, PeerState> peers_;
  std::map<uint64_t, ConnState> conns_;
  std::mt19937_64 rng_{0xC4A05};
  std::map<int64_t,
           std::deque<std::pair<std::chrono::steady_clock::time_point,
                                std::string>>>
      chaos_queue_;
  std::mutex mu_;
  std::condition_variable cv_;
  std::deque<CryptoCmd> q_;
  std::deque<CryptoCmd> local_;  // consumer-side drain scratch
};

// One event-loop shard thread.
class NetShard {
 public:
  NetShard(NetShards* owner, int idx) : owner_(owner), idx_(idx) {}
  ~NetShard();
  bool bind_listener(int port, bool reuseport, int* bound_port);
  void push(LoopCmd&& c, bool force);
  void run();  // thread body

  std::atomic<int64_t> wakeups{0};       // per-shard epoll wakeups
  // Frames handed to queue_bytes and send() calls made (this thread is
  // the one writer; NetShards adds the shards' up for the scrape).
  std::atomic<int64_t> frames_out{0};
  std::atomic<int64_t> send_calls{0};
  std::atomic<int64_t> conns_open{0};
  std::atomic<int64_t> backpressure{0};  // drops + backed-up episodes
  std::atomic<int64_t> replies_dropped{0};
  FrontClock clock;                      // pbft_shard_<stage>_us_total

 private:
  void process_cmds();
  void accept_ready();
  void handle_readable(Conn& c);
  void process_buffer(Conn& c);
  bool handle_prologue_frame(Conn& c, std::string payload);
  bool reject_conn(Conn& c, const std::string& reason);
  void offload_established(Conn& c, int64_t dest);
  void queue_bytes(Conn& c, const std::string& framed);
  void flush(Conn& c);
  void mark_closed(Conn& c);
  void finish_connect(Conn& c);
  void register_conn(Conn& c);
  void dial_peer(int64_t dest, const std::string& addr);
  void start_reply_dial(const std::string& addr, std::string payload);
  void reply_dial_now(const std::string& addr, std::string payload);
  void pump_reply_backlog();
  void sweep();  // per-shard sweep_conns (ISSUE 13 satellite)

  NetShards* owner_;
  int idx_;
  int listen_fd_ = -1;
  std::unique_ptr<Poller> poller_;
  WakeFd wake_;
  std::vector<std::unique_ptr<Conn>> conns_;        // accepted
  std::map<int64_t, std::unique_ptr<Conn>> peers_;  // dialed (dest%N==idx)
  // Closed peer conns parked until the end-of-pass sweep: a stale poller
  // event this pass may still reference the object, but the dest slot
  // must free immediately so a redial isn't deferred a full pass.
  std::vector<std::unique_ptr<Conn>> graveyard_;
  std::map<uint64_t, Conn*> by_token_;
  uint64_t conn_seq_ = 0;
  BufferPool pool_;
  CmdQueue<LoopCmd> cmds_{65536};
  std::vector<PollerEvent> events_;
  size_t connecting_count_ = 0;
  // Per-shard one-shot reply-dial pacing (mirrors the single-loop
  // policy; the budget is per shard by design — ISSUE 13 satellite).
  struct QueuedReply {
    std::string addr;
    std::string payload;
    std::chrono::steady_clock::time_point enqueued;
  };
  std::deque<QueuedReply> reply_backlog_;
  size_t reply_dials_in_flight_ = 0;
  std::set<std::string> reply_addrs_in_flight_;
  std::deque<LoopCmd> local_;
  // The connections a drained stretch of commands queued frames for
  // (Conn::touched): flushed once, after the stretch.
  std::vector<Conn*> touched_;
  void flush_touched();

  friend class NetShards;
};

// The owner: N shards + N pipelines + the K-side (consensus) handoff.
class NetShards {
 public:
  NetShards(const ClusterConfig& cfg, int64_t id, const uint8_t seed[32],
            std::atomic<bool>* stopping, int nshards);
  ~NetShards();

  bool start(int* listen_port_out);
  void stop_join();
  // Pre-start only (threads read them unlocked afterwards).
  void set_chaos(double drop_pct, int delay_ms, uint64_t seed);

  int wake_fd() const { return k_wake_.fd(); }
  // `oldest` (optional): lowered to the push stamp of the oldest entry
  // drained, where the clocks are on.
  void drain_inbox(std::deque<KInbound>* out,
                   CmdQueue<KInbound>::Stamp* oldest = nullptr);
  // The front-end threads' stage clocks follow the consensus thread's
  // (each looks once a pass).
  void set_clocks_on(bool on) {
    if (clocks_on_.load(std::memory_order_relaxed) != on) {
      clocks_on_.store(on, std::memory_order_relaxed);
    }
  }
  bool clocks_on() const { return clocks_on_.load(std::memory_order_relaxed); }

  // Consensus-thread send entry points.
  void send_peer(int64_t dest, const std::string& addr,
                 const std::shared_ptr<ShardEncoded>& enc);
  void send_gateway_line(int shard, uint64_t conn_id, std::string line);
  void dial_reply(const std::string& addr, std::string payload);

  int n_shards() const { return (int)shards_.size(); }
  int shard_of(int64_t dest) const { return (int)(dest % n_shards()); }
  int64_t shard_wakeups(int i) const;
  int64_t total_wakeups() const;
  int64_t cross_thread_wakes() const;
  int64_t connections_open() const;
  int64_t crypto_queue_depth() const;
  int64_t mac_frames() const;
  int64_t frames_out() const;
  int64_t send_calls() const;
  int64_t mac_rejected() const;
  int64_t backpressure_events() const;
  int64_t chaos_dropped() const;
  int64_t inbox_dropped() const {
    return inbox_dropped_.load(std::memory_order_relaxed);
  }
  // Messages lost at a thread boundary, by kind (pbft_shard_dropped_total
  // is their sum; a healthy run reads 0 of each).
  int64_t pipeline_dropped() const;
  int64_t replies_dropped() const;
  // Stage i of kShardStageNames (kPipeStageNames where `pipes`) as shard
  // k's thread (its pipeline's) last published it, whole microseconds;
  // and the same summed over this replica's shards (pipelines).
  int64_t front_stage_us(bool pipes, int k, int i) const {
    return (pipes ? pipelines_[k]->clock : shards_[k]->clock).stage_ns(i) /
           1000;
  }
  int64_t front_stage_us(bool pipes, int i) const {
    int64_t us = 0;
    for (int k = 0; k < n_shards(); ++k) us += front_stage_us(pipes, k, i);
    return us;
  }
  int64_t broadcast_encodes() const {
    return encodes_total.load(std::memory_order_relaxed);
  }

  // Internal (shard/pipeline side).
  void push_inbound(int shard, KInbound&& in);
  bool stopping() const { return stopping_->load(std::memory_order_relaxed); }
  const ClusterConfig& cfg() const { return cfg_; }
  int64_t id() const { return id_; }
  const uint8_t* seed() const { return seed_; }
  CryptoPipeline& pipeline(int i) { return *pipelines_[i]; }
  NetShard& shard(int i) { return *shards_[i]; }
  // Fast-path key table (ISSUE 14): sender-side lane keys per
  // mac-negotiated dialed link, registered by the owning SHARD thread at
  // prologue completion and read (snapshot) by whichever pipeline builds
  // a broadcast's shared MAC vector — the only cross-shard MAC state.
  bool fastpath_mac() const { return fastpath_mac_; }
  void set_mac_key(int64_t dest, const uint8_t key[32]);
  void erase_mac_key(int64_t dest);
  std::map<int64_t, std::array<uint8_t, 32>> mac_key_snapshot() const;

  std::atomic<int64_t> encodes_total{0};

 private:
  ClusterConfig cfg_;
  bool fastpath_mac_ = false;
  mutable std::mutex mac_mu_;
  std::map<int64_t, std::array<uint8_t, 32>> mac_send_keys_;
  int64_t id_;
  uint8_t seed_[32];
  std::atomic<bool>* stopping_;
  std::vector<std::unique_ptr<NetShard>> shards_;
  std::vector<std::unique_ptr<CryptoPipeline>> pipelines_;
  std::vector<std::unique_ptr<CmdQueue<KInbound>>> inbox_;  // SPSC per shard
  WakeFd k_wake_;
  std::atomic<int64_t> inbox_dropped_{0};
  std::atomic<bool> clocks_on_{false};
  std::vector<std::thread> threads_;
  bool started_ = false;
  bool joined_ = false;
};

}  // namespace pbft
