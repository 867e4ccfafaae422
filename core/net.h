// TPU-era replacement for the reference's libp2p stack (SURVEY.md §5
// "Distributed communication backend"): consensus messaging is a host-side
// concern — plain TCP with 4-byte big-endian length-prefixed canonical-JSON
// frames between replicas (the reference used varint-framed JSON over libp2p
// substreams, reference src/protocol_config.rs:49-101), a static peer table
// from network.json (which the reference shipped but never read, SURVEY.md
// §2), and a raw-JSON client gateway preserving the reference's client
// contract: JSON request in over TCP, reply *dialed back* to the client's
// advertised address (reference src/client_handler.rs:75-84, README.md:33-43).
//
// Single-threaded event loop; the consensus core stays I/O-free and
// deterministic. Each loop iteration drains every readable socket into the
// replica's inbox, then runs ONE verifier batch over everything that
// arrived — the batching window that feeds the TPU verifier (BASELINE.json
// north_star) emerges naturally from socket-level concurrency.
//
// ISSUE 10 (scale-out): readiness comes from a persistent-registration
// Poller — edge-triggered epoll on Linux (fds registered once at
// accept/dial, deregistered at close), with a level-triggered poll()
// fallback for non-epoll hosts (PBFT_NET_POLL=1 forces it, which is the
// parity-test lever). Connections carry reusable pooled read buffers and
// a bounded outbound block queue with partial-write backpressure, and a
// client-gateway tier (pbft_tpu/net/gateway.py) multiplexes thousands of
// client identities onto a few persistent framed links whose replies fan
// back over the SAME link instead of per-reply dial-backs.
//
// ISSUE 13 (multi-core): with net_threads > 1 (network.json / pbftd
// --net-threads) the socket work moves to N event-loop shard threads
// (SO_REUSEPORT accept sharding, per-fd ownership) and AEAD seal/open +
// payload codec work to per-shard crypto pipelines (core/net_shard.h);
// THIS class then runs only the consensus thread — Replica, verify
// windows, timers, tracing, metrics — fed by bounded SPSC queues with an
// eventfd wake. net_threads == 1 is the classic single-threaded loop,
// byte-for-byte the pre-ISSUE-13 behavior.
#pragma once

#include <array>
#include <atomic>
#include <chrono>
#include <cmath>
#include <cstdint>
#include <cstdio>
#include <deque>
#include <map>
#include <memory>
#include <optional>
#include <random>
#include <set>
#include <string>
#include <utility>
#include <vector>

#include "discovery.h"
#include "metrics.h"
#include "replica.h"
#include "secure.h"
#include "verifier.h"

namespace pbft {

// Stream-socket option discipline (ISSUE 10 satellite): EVERY data socket
// gets TCP_NODELAY (consensus frames are latency-critical and small; one
// Nagle stall per hop dwarfs a round), every listener SO_REUSEADDR.
// scripts/pbft_lint.py (analysis/sockets.py) statically requires each
// socket()/accept() site in core/ to call one of these.
void tune_stream_socket(int fd);
void tune_listen_socket(int fd);

// Gateway-routed client identities carry this prefix (mirrored by
// pbft_tpu/net/gateway.py GATEWAY_CLIENT_PREFIX; constants lint): such a
// "client address" is a routing token, never a dialable host:port — a
// reply that cannot be routed over a gateway link is dropped for the
// retransmission path, not dialed.
inline constexpr const char* kGatewayClientPrefix = "gw/";

// Health-introspection contract (ISSUE 16; Python mirrors in
// pbft_tpu/utils/trace_schema.py + pbft_tpu/analysis/health.py,
// constants lint pairs). kHealthDocVersion stamps the metrics_json
// status surface so pbft_top / the detector library can refuse
// snapshots from a runtime speaking a different document shape.
// kHealthStallSeconds is the silent-stall threshold: pending work with
// executed_upto flat this long trips the detector. kHealthSnapshotIntervalS
// is the default poll cadence for pbft_top / endurance_soak snapshots.
inline constexpr int kHealthDocVersion = 1;
inline constexpr int kHealthStallSeconds = 5;
inline constexpr int kHealthSnapshotIntervalS = 2;

// 4-byte big-endian length prefix + payload (the framed wire format).
// Shared by the single-threaded loop and the shard/pipeline tier.
std::string frame_payload(const std::string& payload);

// Bounded-outbound / send-block coalescing budgets (values live in
// net.cc next to their policy comments; the constants lint reads them
// there — these accessors let core/net_shard.cc share them).
size_t max_conn_outbound();
size_t max_send_block();

// Reusable receive buffer: consumption advances an offset instead of
// erase(0, n)'s per-frame memmove; the storage compacts lazily and resets
// (capacity retained) when drained. Backing strings come from the
// server's BufferPool so connection churn doesn't malloc per accept.
struct RecvBuf {
  std::string data;
  size_t pos = 0;

  size_t size() const { return data.size() - pos; }
  bool empty() const { return pos == data.size(); }
  uint8_t at(size_t i) const { return (uint8_t)data[pos + i]; }
  void append(const char* p, size_t n) {
    if (pos > 65536 && pos > data.size() / 2) {  // lazy compaction
      data.erase(0, pos);
      pos = 0;
    }
    data.append(p, n);
  }
  void consume(size_t n) {
    pos += n;
    if (pos == data.size()) {
      data.clear();  // keeps capacity: the buffer is the pool unit
      pos = 0;
    }
  }
  std::string take(size_t n) {
    std::string s = data.substr(pos, n);
    consume(n);
    return s;
  }
  size_t find(char ch) const {
    auto r = data.find(ch, pos);
    return r == std::string::npos ? std::string::npos : r - pos;
  }
  std::string str() const { return data.substr(pos); }
  void reset() {
    data.clear();
    pos = 0;
  }
};

// Outbound block queue: frames coalesce into pooled blocks; a partial
// write advances front_pos (no erase-from-front memmove). `bytes` is the
// total queued — the bounded-outbound drop policy reads it.
struct SendQueue {
  std::deque<std::string> blocks;
  size_t front_pos = 0;
  size_t bytes = 0;
  bool empty() const { return bytes == 0; }
};

// Bounded free-list of grown std::strings, reused across connections and
// send blocks (ISSUE 10: firehose-rate conn churn must not pay a
// malloc/free cycle per accept or per queued frame).
class BufferPool {
 public:
  std::string acquire() {
    if (bufs_.empty()) return std::string();
    std::string s = std::move(bufs_.back());
    bufs_.pop_back();
    s.clear();
    return s;
  }
  void release(std::string&& s) {
    if (bufs_.size() < kMaxPooled && s.capacity() >= 512 &&
        s.capacity() <= kMaxRetainedCap) {
      bufs_.push_back(std::move(s));
    }
  }

 private:
  static constexpr size_t kMaxPooled = 64;
  static constexpr size_t kMaxRetainedCap = 1u << 20;
  std::vector<std::string> bufs_;
};

// One readiness event from the Poller backend. `tag` is whatever the
// caller registered: a Conn* or one of the ReplicaServer sentinel tags.
struct PollerEvent {
  uint64_t tag;
  bool readable;
  bool writable;
  bool error;
};

// Persistent-registration readiness backend (the ISSUE 10 tentpole):
// register each fd ONCE at accept/dial, wait for events, deregister at
// close — instead of rebuilding a pollfd array every loop iteration.
// Two implementations in net.cc:
//   EpollPoller — Linux, edge-triggered for connections (EPOLLIN |
//                 EPOLLOUT | EPOLLET armed once; writes are flushed
//                 eagerly at enqueue, so EPOLLOUT edges only matter
//                 after a partial write), level-triggered for the
//                 listener/metrics/verifier sentinels.
//   PollPoller  — portable fallback (and the PBFT_NET_POLL=1 parity
//                 lever): a pollfd table maintained INCREMENTALLY
//                 (O(1) add/remove/write-interest via an fd index map),
//                 so even the fallback never rebuilds per iteration.
class Poller {
 public:
  virtual ~Poller() = default;
  virtual const char* name() const = 0;
  // `edge` requests edge-triggered read+write registration where the
  // backend supports it; sentinel fds pass false (level-triggered read).
  virtual bool add(int fd, uint64_t tag, bool edge) = 0;
  virtual void remove(int fd) = 0;
  // Level-triggered fallback only: arm/disarm write readiness for fd.
  // No-op on the edge-triggered backend.
  virtual void set_write_interest(int fd, bool want) = 0;
  // Fills `out` with ready events; returns poll()/epoll_wait() semantics
  // (<0 error, 0 timeout).
  virtual int wait(std::vector<PollerEvent>* out, int timeout_ms) = 0;
};

// epoll on Linux unless PBFT_NET_POLL=1 (or epoll_create fails); the
// portable poll() backend otherwise.
std::unique_ptr<Poller> make_poller();

// One buffered non-blocking TCP connection.
struct Conn {
  int fd = -1;
  RecvBuf rbuf;
  SendQueue out;
  bool raw_json = false;   // client-gateway mode (sniffed: first byte '{')
  bool sniffed = false;
  bool closed = false;
  // Nonblocking connect in flight: the single-threaded event loop must
  // never block on a dial (a black-holed peer or a client advertising an
  // unroutable reply address would stall every replica duty for the TCP
  // connect timeout). While connecting, writes buffer and flush() no-ops;
  // poll_once finishes the connect on POLLOUT or reaps it at the deadline.
  bool connecting = false;
  std::chrono::steady_clock::time_point connect_deadline{};
  // Dial-back replies: one-shot connections closed once wbuf drains.
  bool close_when_flushed = false;
  std::string reply_addr;  // for the per-address in-flight dedup
  // Peer-link prologue state (core/secure.cc): every framed peer link
  // starts with a version-carrying hello; secure clusters run the full
  // handshake and seal every subsequent frame.
  int64_t peer_dest = -1;  // >= 0 on dialed (outbound) links
  bool hello_seen = false;  // inbound: version hello consumed
  // Negotiated payload codec for this dialed link: binary-v2 once the
  // peer's hello (plaintext hello-ack or secure hello_r) offered "bin2".
  // Frames sent before the offer arrives go as JSON; receivers detect
  // the codec per frame from the payload's first byte.
  bool codec_binary = false;
  // Fast-path negotiation (ISSUE 14): peer_mac latches when the hello
  // offered the MAC authenticator mode (and this node offers it);
  // mac_ready flips once the handshake established the lane keys —
  // outbound hot messages then go as MAC-vector frames (dialed links)
  // and inbound MAC frames verify their lane (accepted links).
  bool peer_mac = false;
  bool mac_ready = false;
  // Inbound link whose hello carried role=gateway (ISSUE 10): framed
  // client requests arrive here, and replies for the clients it forwarded
  // fan BACK over this same link instead of per-reply dial-backs.
  bool gateway = false;
  uint64_t link_id = 0;  // gateway_links_ key (stable across the map)
  // Latch for pbft_write_backpressure_events_total: one count per
  // backed-up episode, cleared when the queue drains.
  bool backpressured = false;
  // Frames were queued here by a stretch of work that flushes ONCE at its
  // end (ReplicaServer::emit, NetShard::process_cmds): the conn is on
  // that stretch's list of touched connections until the flush.
  bool touched = false;
  std::unique_ptr<SecureChannel> chan;
  std::vector<std::string> pending;  // outbound payloads queued pre-handshake
  // Multi-core mode only (core/net_shard.h). shard_token keys the conn in
  // its shard's registries; offloaded flips once the link prologue is
  // done and frames flow to the crypto pipeline; out_gauge mirrors the
  // send queue's byte count so the pipeline can run bounded-outbound
  // admission BEFORE the AEAD seal without touching shard-owned state.
  uint64_t shard_token = 0;
  bool offloaded = false;
  std::shared_ptr<std::atomic<int64_t>> out_gauge;
};

// A message mid-fan-out: canonical JSON and binary-v2 encodings are
// computed lazily, AT MOST ONCE each, however many peers the message goes
// to (the serialize-once invariant; `encodes` feeds
// pbft_broadcast_encodes_total). Secure links seal per peer over the
// shared plaintext.
struct EncodedOut {
  const Message* m;
  std::string json;
  std::string binary;
  bool binary_tried = false;
  bool binary_ok = false;
  // MAC-vector variant (ISSUE 14): computed AT MOST ONCE per broadcast
  // over the sender-side lane keys of every mac-negotiated link — the
  // serialize-once invariant extended to the authenticator mode. A peer
  // whose link joins mid-fan-out misses its lane and falls back to
  // signature verification (the sig rides in the frame).
  std::string mac;
  bool mac_tried = false;
  bool mac_ok = false;
  int64_t encodes = 0;

  explicit EncodedOut(const Message* msg) : m(msg) {}
  const std::string& json_payload() {
    if (json.empty()) {
      json = message_canonical(*m);
      ++encodes;
    }
    return json;
  }
  const std::string* binary_payload() {
    if (!binary_tried) {
      binary_tried = true;
      binary_ok = message_to_binary(*m, &binary);
      if (binary_ok) ++encodes;
    }
    return binary_ok ? &binary : nullptr;
  }
  const std::string* mac_payload(
      const std::map<int64_t, std::array<uint8_t, 32>>& keys);
};

// Replica-level Byzantine behavior modes (--fault, ISSUE 5). Mirrors the
// simulation's FAULT_MODES and the asyncio runtime's --fault so a chaos
// scenario scripts identically against either daemon:
//   kSigCorrupt — every outgoing signature corrupted (the old --byzantine);
//   kMute       — receives but never sends (protocol frames AND replies);
//   kStutter    — sends normally, plus seeded replays of stale messages;
//   kEquivocate — the primary sends CONFLICTING validly-signed
//                 pre-prepares for one (view, seq) to different backups.
enum class FaultMode { kNone, kSigCorrupt, kMute, kStutter, kEquivocate };

// "" / "none" -> kNone, "sig-corrupt"/"byzantine" -> kSigCorrupt, etc.
// Returns false on an unknown mode name.
bool fault_mode_from_string(const std::string& s, FaultMode* out);

// Where the loop thread's wall time goes, by KIND OF WORK (ISSUE 38). The
// stages are exclusive and nested: a frame read inside handle_readable is
// dispatched, executed, signed, flushed and answered before that call
// returns, so a Scope charges what has elapsed to the stage that was
// running, switches, and switches back when it ends. One clock read a
// switch, plain integers, no registry lookup: the registry's counters are
// brought up to date where a scrape or /status is rendered
// (ReplicaServer::fold_counters). `on` follows metrics_.enabled ||
// trace_fp_ (poll_once sets it once a pass); off, a Scope is one branch.
// The seven stages sum to the elapsed monotonic time since the clock came
// on. With --net-threads above 1 it covers the consensus thread alone
// (the socket work is the shards').
enum LoopStage : int {
  kLoopWait,      // inside poller_->wait
  kLoopRead,      // socket reads, frame decode, link authentication
  kLoopProtocol,  // every call into Replica: state machine, digests, signing
  kLoopWal,       // flush_wal with records pending: write + fsync
  kLoopSend,      // emit after the flush: encode, MAC tags, queue, send()
  kLoopVerify,    // the verify inbox: pending_items, begin_batch, verdicts
  kLoopOther,     // the rest of a pass: scrapes, sweeps, timers' arithmetic
  kLoopStages
};
inline constexpr const char* kLoopStageNames[kLoopStages] = {
    "wait", "read", "protocol", "wal", "send", "verify", "other"};
// Of these, the slots a front-end thread's clock uses (net_shard.h
// FrontClock: a shard thread's wait/read/send/other, a pipeline thread's
// wait/decode/encode/other).
constexpr int kFrontStages = 4;

struct LoopClock {
  bool on = false;
  int stage = kLoopOther;
  std::chrono::steady_clock::time_point since{};
  std::array<int64_t, kLoopStages> ns{};
  int64_t switches = 0;

  // Follow the switch that turns the clock on; time before it is nobody's.
  void set_on(bool want) {
    if (want == on) return;
    on = want;
    if (on) since = std::chrono::steady_clock::now();
  }
  // Charge what has elapsed to the running stage; returns the instant.
  std::chrono::steady_clock::time_point sync() {
    const auto now = std::chrono::steady_clock::now();
    ns[stage] +=
        std::chrono::duration_cast<std::chrono::nanoseconds>(now - since)
            .count();
    since = now;
    return now;
  }
  int64_t total_ns() const {
    int64_t t = 0;
    for (int64_t v : ns) t += v;
    return t;
  }
  // One stage switch: returns the stage that was running.
  int switch_to(int s) {
    sync();
    ++switches;
    const int prev = stage;
    stage = s;
    return prev;
  }

  // Run on in stage s until the next enter or Scope: for a thread that
  // works through a queue of mixed commands (net_shard.cc), one clock read
  // where two neighbours differ in kind and none where they do not.
  void enter(int s) {
    if (on && stage != s) switch_to(s);
  }

  class Scope {
   public:
    Scope(LoopClock& c, int s)
        : c_(c), prev_(c.on && c.stage != s ? c.switch_to(s) : -1) {}
    ~Scope() {
      if (prev_ >= 0) c_.switch_to(prev_);
    }
    Scope(const Scope&) = delete;
    Scope& operator=(const Scope&) = delete;

   private:
    LoopClock& c_;
    int prev_;
  };
};

class NetShards;  // multi-core front end (core/net_shard.h)

class ReplicaServer {
 public:
  ReplicaServer(ClusterConfig cfg, int64_t id, const uint8_t seed[32],
                std::unique_ptr<Verifier> verifier);
  ~ReplicaServer();

  // Bind + listen on the replica's configured port. Returns false on error.
  bool start();
  // Run until stop() (from a signal handler) — poll_once in a loop.
  void run();
  // One event-loop iteration: poll, read, batch-verify, emit.
  void poll_once(int timeout_ms);
  void stop() { stopping_ = true; }
  bool stopped() const { return stopping_; }

  Replica& replica() { return *replica_; }
  int listen_port() const { return listen_port_; }
  // Which readiness backend this server runs on ("epoll-et" or "poll") —
  // the epoll-vs-poll parity arm in core_test asserts both paths.
  const char* net_backend() const;
  // One JSON metrics line (counters + queue depths), extended into the
  // versioned health document (ISSUE 16): health_version, uptime,
  // RSS/fd/WAL-bytes resource readings, progress watermarks and chain/
  // state digests. Non-const: rendering refreshes the last-progress
  // tracker and the health gauges (lazy — an unscraped replica pays
  // nothing for them).
  std::string metrics_json();

  // Prometheus scrape surface (metric names contracted with the Python
  // runtime by pbft_tpu/utils/trace_schema.py): call before start() to
  // listen on `port` (0 = ephemeral) and serve the registry as plaintext.
  // Enabling this turns the metrics registry on; consensus-phase spans
  // additionally feed the trace file when set_trace_file is active.
  void set_metrics_port(int port) { metrics_port_ = port; }
  int metrics_listen_port() const { return metrics_listen_port_; }
  Metrics& metrics() { return metrics_; }
  // Non-const like metrics_json(): rendering first brings the counters
  // that are kept as plain integers up to date (fold_counters).
  std::string metrics_prometheus();
  // Where the loop thread's time has gone so far (ISSUE 38).
  const LoopClock& loop_clock() const { return loop_clock_; }

  // Wedged-async-verifier bound: an inflight remote launch
  // older than this is abandoned — connection dropped, batch re-verified
  // on the CPU safety net, verify_deadline_fired traced + counted.
  // Generous default: a first XLA compile can legitimately take tens of
  // seconds; the fallback is safe (the dropped reply goes nowhere) but
  // thrashing it would waste the service's warm cache. 0 disables.
  void set_verify_deadline_ms(int ms) { verify_deadline_ms_ = ms; }

  // Request/progress timer (PBFT §4.4 liveness): when a client request is
  // waiting (forwarded to the primary, or accepted pre-prepares sit
  // unexecuted) and no progress happens within `ms`, the replica starts a
  // view change; the timeout doubles per consecutive failed view
  // (§4.5.2's exponential backoff). 0 disables.
  void set_view_change_timeout(int ms) { vc_timeout_ms_ = ms; }

  // Enable UDP-multicast peer discovery ("group:port") — the mDNS
  // equivalent: peers whose configured port is 0 are addressed from
  // beacons instead of network.json. Call before start().
  void enable_discovery(const std::string& target) { discovery_target_ = target; }

  // Structured JSONL tracing (batch boundaries + view changes only; the
  // reference logged inside the poll hot loop, SURVEY.md §5 — we don't).
  // Returns false (with a stderr warning) if the file cannot be opened;
  // closes any previously set sink.
  bool set_trace_file(const std::string& path);

  // Fault injection (ISSUE 5): install a Byzantine behavior mode for this
  // daemon. set_byzantine is the legacy --byzantine spelling of the
  // sig-corrupt mode. Honest replicas must tolerate any single mode at
  // <= f faulty: reject what is rejectable, vote out what stalls.
  void set_fault(FaultMode m) { fault_mode_ = m; }
  void set_byzantine(bool b) {
    fault_mode_ = b ? FaultMode::kSigCorrupt : FaultMode::kNone;
  }

  // Durable replica recovery (ISSUE 15): open {dir}/replica-{id}.wal
  // (group-commit fsync per cfg.wal_fsync), replay it, reinstall the
  // persisted safety state into the replica, and wire the no-
  // contradiction guards. Call before start(). Returns false when the
  // log is corrupt/unwritable. recovered_from_wal() reports whether the
  // replay found pre-crash state to reinstall.
  bool enable_wal(const std::string& dir);
  bool recovered_from_wal() const { return recovered_from_wal_; }

  // Seeded link-level chaos (ISSUE 5): every outbound peer frame is
  // dropped with probability drop_pct, and (when delay_ms > 0) held for a
  // uniform 0..delay_ms before hitting the socket — per-destination FIFO,
  // so secure-channel frame order (the AEAD nonce sequence) is preserved.
  // Deterministic per (seed): the same seed replays the same drop/delay
  // pattern for the same frame sequence.
  void set_chaos(double drop_pct, int delay_ms, uint64_t seed) {
    chaos_drop_pct_ = drop_pct;
    chaos_delay_ms_ = delay_ms;
    chaos_seed_ = seed;
    chaos_rng_.seed(seed);
  }

 private:
  void accept_ready();
  void handle_readable(Conn& c);
  // Register a freshly created conn with the poller (dials additionally
  // arm write readiness for connect completion on the fallback backend).
  void register_conn(Conn& c);
  // Append framed bytes to c's outbound queue, coalescing into pooled
  // blocks. Callers flush() afterwards, or flush_once_an_emit() on the
  // hot path (edge-triggered discipline: the eager flush IS the common
  // write path; poller write events only resume after a partial write).
  void queue_bytes(Conn& c, const std::string& framed);
  // Bounded-outbound admission (ISSUE 10 satellite): false when the
  // conn's queue is over budget — the frame is dropped and counted
  // (PBFT retransmission absorbs the loss like any link drop).
  bool outbound_has_room(Conn& c);
  void count_backpressure();
  // Route a reply over a gateway link (framed raw-JSON payload).
  void send_gateway_reply(Conn& g, const std::string& payload);
  // Remember which gateway link forwarded for `client` (bounded map).
  void note_gateway_route(const std::string& client, uint64_t link_id);
  // (De)register the in-flight async verifier fd with the poller. The fd
  // may already be closed by the verifier at removal time; that is safe
  // single-threaded (nothing reuses the number before the remove runs).
  void register_verifier_fd();
  void unregister_verifier_fd();
  // End-of-iteration sweep: reap overdue nonblocking connects, erase
  // closed conns (returning their buffers to the pool), refresh the
  // connections-open gauge and the connecting count.
  void sweep_conns();
  // Resolve an in-flight nonblocking connect (SO_ERROR check) and flush
  // whatever buffered while it completed.
  void finish_connect(Conn& c);
  // Extract complete frames / JSON lines from c.rbuf into the replica.
  void process_buffer(Conn& c);
  // One framed peer-link payload: handshake routing (hello/auth/reject),
  // AEAD open on secure links, then protocol dispatch. Returns false when
  // the connection was closed.
  bool handle_peer_frame(Conn& c, std::string payload);
  // Send a reject frame naming the reason, then close. Always false.
  bool reject_conn(Conn& c, const std::string& reason);
  // Log + close (no reject frame: the link is beyond a polite refusal).
  bool fail_conn(Conn& c, const std::string& reason);
  void flush(Conn& c);
  // The hot path's flush (votes, gateway replies): inside an emit() the
  // conn is put on the list of touched connections and flushed once when
  // the emit has queued everything for it; outside one it is flushed now.
  void flush_once_an_emit(Conn& c);
  void flush_touched();
  // The verify step of a pass, at its end: launch the span of the inbox
  // that no launch has taken, THEN work through the verdicts the pass
  // kept (and launch what that delivery queued for the replica itself,
  // if nothing went ahead of it).
  void run_verify_batch();
  // Cut the unlaunched span and ship it (async), or verify it here
  // (blocking branch: kept verdicts are applied before it, never after).
  void launch_verify_span();
  // Drain verdict bytes from an async (RemoteVerifier) launch; on
  // completion keep the verdicts for the pass's end, on transport failure
  // keep the CPU safety net's for the span that was on the wire.
  void finish_verify_async();
  // The span on the wire has its verdicts: take it off the wire and keep
  // them until run_verify_batch (anything still kept is applied first:
  // spans are delivered in inbox order).
  void keep_verdicts(std::vector<uint8_t> verdicts);
  void apply_kept_verdicts();
  // Shared verdict accounting for the sync and async paths: counters,
  // deliver + emit, then the trace line (secs: launch -> verdicts in
  // hand; ahead: a launch went out while these verdicts were kept). One
  // verdict an item. `began`: when the delivery of a KEPT span began
  // (apply_kept_verdicts' clock read); from there to the last send is
  // pbft_verdict_apply_seconds, once a batch. Null on the blocking branch.
  void deliver_verified(double secs, bool ahead, std::vector<uint8_t> verdicts,
                        const std::chrono::steady_clock::time_point* began);
  // A call into Replica, charged to the loop clock's `protocol` stage.
  template <class F>
  auto in_protocol(F&& call) {
    LoopClock::Scope in(loop_clock_, kLoopProtocol);
    return call();
  }
  void emit(Actions&& actions);
  void send_to(int64_t dest, const Message& m);
  // Shared by send_to and the broadcast fan-out: pick the link codec,
  // reuse (or lazily compute) the encoding, seal per peer, flush.
  void send_encoded(int64_t dest, EncodedOut& enc);
  void dial_reply(const std::string& client_addr, const ClientReply& reply);
  // One raw-JSON line toward a client, by whatever channel its address
  // names: the gateway link that forwarded for a "gw/" token (exact
  // route, else fan-out), or a one-shot dial-back. Shared by replies and
  // the ISSUE 12 overloaded notices.
  void send_client_line(const std::string& client_addr,
                        const std::string& payload);
  // Admission control at client-request ingest (ISSUE 12): true when the
  // request was rejected (explicit overloaded line sent, request
  // dropped). Retransmissions always pass. Mirrors net/server.py.
  bool maybe_reject_overload(const ClientRequest& req);
  // Start one reply dial (nonblocking) if the in-flight budget allows,
  // else queue it in reply_backlog_.
  void start_reply_dial(const std::string& addr, std::string payload);
  bool reply_budget_free() const;
  void reply_dial_now(const std::string& addr, std::string payload);
  // Launch queued reply dials while under the in-flight budget.
  void pump_reply_backlog();
  // THE close path for conns: closes the fd, marks closed, and keeps the
  // O(1) reply-dial in-flight counter balanced.
  void mark_closed(Conn& c);
  int peer_fd(int64_t dest);  // cached outbound connection (lazy dial)

  void check_progress_timer();
  // Multi-core mode (ISSUE 13): the address a peer link should dial
  // (config table or discovery), "" when unknown — shared by the
  // single-loop lazy dial and the sharded send path.
  std::string peer_addr(int64_t dest);
  // Fan one message out to every peer, serialize-once, on whichever
  // front end (single loop / shard tier) is active. Returns the shared
  // sharded encoding when one was built (equivocate reuses the helper).
  void broadcast_message(const Message& m);
  // Drain the shard->consensus inbox: parsed messages into the replica,
  // gateway link lifecycle into the route tables.
  void process_shard_inbound();
  // Fold the shards' relaxed-atomic counters into the (single-writer)
  // metrics registry as monotonic increments; refresh the gauges.
  void aggregate_shard_metrics();
  // Chaos link gate: true when the framed bytes should be written to the
  // peer NOW; false when they were dropped (counted) or queued for a
  // delayed release. Called with the final on-wire frame (post-seal), so
  // per-destination FIFO release preserves AEAD ordering.
  bool chaos_pass(int64_t dest, const std::string& framed);
  // Release delayed frames whose deadline arrived onto their peer links.
  void pump_chaos_queue(std::chrono::steady_clock::time_point now);
  // The --fault equivocate engine: variant B of the primary's own
  // pre-prepare (operations mutated, digest recomputed, RE-SIGNED — both
  // variants verify, which is what makes equivocation an attack).
  Message equivocate_variant(const PrePrepare& pp);
  void count_fault();
  // Seal the primary's partial batch once it has waited batch_flush_us
  // (ClusterConfig::batch_flush_us; 0 = seal on the next pass). poll_once
  // clamps its timeout to the flush deadline, like the verify window.
  void check_batch_flush(std::chrono::steady_clock::time_point now);
  // Batching counters (pbft_requests_executed_total /
  // pbft_consensus_rounds_total): recorded as deltas of the replica's
  // executed / rounds_executed counters after every emit.
  void observe_execution_metrics();

  ClusterConfig cfg_;
  int64_t id_;
  uint8_t seed_[32];  // identity seed: signs secure-link handshakes too
  std::unique_ptr<Verifier> verifier_;
  std::unique_ptr<Replica> replica_;
  // Write-ahead log (ISSUE 15): flushed at the emit boundary (before any
  // of a pass's votes reach a socket) and once per poll pass; the
  // counters below are last-seen snapshots for the metric deltas.
  std::unique_ptr<Wal> wal_;
  std::string wal_path_;  // on-disk file (pbft_wal_disk_bytes stat target)
  bool recovered_from_wal_ = false;
  double recovery_seconds_ = 0.0;
  int64_t seen_wal_appends_ = 0;
  int64_t seen_wal_fsyncs_ = 0;
  int64_t seen_wal_bytes_ = 0;
  // Group-commit point: write+fsync everything noted since the last
  // flush, then fold the wal counters into the metrics registry.
  void flush_wal();
  // apply_s < 0: not measured (the blocking branch).
  void trace_batch(int64_t size, int64_t rejected, double secs, bool ahead,
                   double apply_s);
  void trace_view_change(int backoff);
  // Request-level waterfall events (ISSUE 9; schemas in
  // pbft_tpu/utils/trace_schema.py): request arrival, the primary's batch
  // seal (with how long the batch waited open and the [client, req_ts]
  // join keys), and the reply leaving toward the client. Each also feeds
  // the black-box flight recorder when it is enabled.
  void trace_request_rx(const ClientRequest& req);
  void trace_batch_sealed(const PrePrepare& pp);
  void trace_reply_tx(const ClientReply& reply);
  // Replica::view_hook target: view_change_sent / new_view_installed
  // trace events + flight records (ROADMAP item 4 view-change spans).
  void on_view_event(const char* ev, int64_t v);
  // Consensus-phase spans (Replica::phase_hook target): stamps each
  // transition; at "executed" observes the per-phase latency histograms
  // and emits one consensus_span trace event (utils/trace_schema.py).
  void on_phase(const char* phase, int64_t view, int64_t seq);
  // Replica::commit_hook target (tentative mode): the committed floor
  // passed seq; observes pbft_tentative_commit_lag_seconds against the
  // stamp on_phase kept at that sequence number's "executed".
  void on_commit_floor(int64_t seq);
  // Accept + answer scrapes (one-shot: write response, close). Routes on
  // the request line: "/status" serves metrics_json() as JSON, anything
  // else the Prometheus text rendering.
  void serve_metrics_ready();
  // Lazy health refresh (ISSUE 16): advance the last-progress tracker
  // against replica_->executed_upto() and push the resource/progress
  // health gauges into the registry. Called whenever the status surface
  // renders (metrics_json / Prometheus scrape).
  void refresh_health();
  // Bring the registry's counters that the loop keeps as plain integers up
  // to date (ISSUE 38): the loop clock's stages, passes, frames in, MAC
  // frames, requests over gateway links. Called with refresh_health, so
  // no per-frame or per-stage-switch path looks a name up.
  void fold_counters();
  // A monotonic total the loop keeps -> the registry counter's increment.
  void fold_delta(int64_t now_abs, int64_t* seen, const char* name);
  // Abandon an over-deadline inflight async verify (see
  // set_verify_deadline_ms); no-op unless wedged.
  void check_verify_deadline(std::chrono::steady_clock::time_point now);

  FILE* trace_fp_ = nullptr;
  std::string discovery_target_;
  std::unique_ptr<Discovery> discovery_;
  std::map<int64_t, std::string> discovered_addrs_;
  std::chrono::steady_clock::time_point last_beacon_{};
  int vc_timeout_ms_ = 0;
  bool timer_armed_ = false;
  FaultMode fault_mode_ = FaultMode::kNone;
  // Fast-path mode (ISSUE 14): whether this node offers the MAC
  // authenticator mode, the sender-side lane key per mac-negotiated
  // dialed link (the shared per-broadcast MAC vector reads the whole
  // table), and the frame tallies.
  bool fastpath_mac_ = false;
  std::map<int64_t, std::array<uint8_t, 32>> mac_send_keys_;
  int64_t mac_frames_ = 0;
  int64_t mac_rejected_ = 0;
  // Last-seen tentative counters for the metric deltas + the rollback
  // flight record.
  int64_t seen_tentative_ = 0;
  int64_t seen_rollbacks_ = 0;
  int64_t seen_seals_refused_ = 0;
  int64_t seen_inline_verifies_ = 0;
  int64_t seen_signs_ = 0;
  // Chaos link state (set_chaos): seeded drop/delay on outbound peer
  // frames, a per-destination FIFO of delayed frames, and the injected
  // fault / dropped frame tallies surfaced in metrics_json.
  double chaos_drop_pct_ = 0.0;
  int chaos_delay_ms_ = 0;
  uint64_t chaos_seed_ = 0xC4A05;  // remembered for the per-shard streams
  std::mt19937_64 chaos_rng_{0xC4A05};
  std::map<int64_t,
           std::deque<std::pair<std::chrono::steady_clock::time_point,
                                std::string>>>
      chaos_queue_;
  int64_t faults_injected_ = 0;
  int64_t chaos_dropped_ = 0;
  // Recently broadcast messages, for the stutter mode's stale replays.
  std::deque<Message> stutter_history_;
  int timer_backoff_ = 1;
  // One VIEW-CHANGE retransmission per backoff level before escalating
  // (ISSUE 12): a deadline expiry mid-view-change first re-broadcasts
  // the pending VIEW-CHANGE verbatim (lost-frame recovery in the SAME
  // view); only the NEXT no-progress expiry escalates and doubles.
  bool timer_retransmitted_ = false;
  int gauged_backoff_ = 1;  // last level pushed to the gauge/flight ring
  std::chrono::steady_clock::time_point timer_deadline_{};
  // State-transfer retry keeps its own deadline: the view-change timer may
  // hold a stale backed-off deadline (up to 64x vc_timeout) that must not
  // delay the first fetch retry.
  bool state_timer_armed_ = false;
  std::chrono::steady_clock::time_point state_timer_deadline_{};
  int64_t timer_exec_snapshot_ = 0;
  int64_t timer_view_snapshot_ = 0;
  // Forwarded-but-unreplied client requests: (client addr, timestamp).
  std::map<std::pair<std::string, int64_t>,
           std::chrono::steady_clock::time_point>
      waiting_requests_;
  int listen_fd_ = -1;
  int listen_port_ = 0;
  // Atomic: stop() is documented as callable from a signal handler
  // (pbftd) and is called cross-thread by core/race_stress.cc — a plain
  // bool is a data race under TSan and unsequenced for the signal case.
  std::atomic<bool> stopping_{false};
  // Reply dials beyond the in-flight budget wait here: un-paced one-shot
  // dials can overflow a client listener's accept backlog and lose
  // replies to SYN drops. Entries expire after a TTL — black-holed
  // attacker addresses pinning the in-flight slots must not delay honest
  // replies beyond the client's retransmit interval (a dropped reply is
  // re-fetched from the reply cache on retransmission, PBFT §4.1).
  struct QueuedReply {
    std::string addr;
    std::string payload;
    std::chrono::steady_clock::time_point enqueued;
  };
  std::deque<QueuedReply> reply_backlog_;
  size_t reply_dials_in_flight_ = 0;
  // At most ONE in-flight dial per address: a client has one outstanding
  // request (PBFT §4.1), so honest traffic never needs two, and a
  // black-holed address can pin at most one slot instead of all of them.
  std::set<std::string> reply_addrs_in_flight_;
  int64_t replies_dropped_ = 0;  // overflow + TTL expiry (metrics_json)
  std::vector<std::unique_ptr<Conn>> conns_;       // accepted (inbound)
  std::map<int64_t, std::unique_ptr<Conn>> peers_;  // dialed (outbound)
  // Readiness backend + per-iteration event scratch (ISSUE 10): fds are
  // registered once at accept/dial and removed at close — no per-pass
  // pollfd rebuild. Created in the constructor so every conn path can
  // register unconditionally.
  std::unique_ptr<Poller> poller_;
  std::vector<PollerEvent> events_;
  BufferPool pool_;  // reusable recv buffers + send blocks
  int verifier_fd_ = -1;  // async verifier fd currently registered
  size_t connecting_count_ = 0;  // nonblocking dials awaiting completion
  int64_t event_wakeups_ = 0;        // poller wait() returns (metrics_json)
  int64_t backpressure_events_ = 0;  // drops + backed-up episodes
  // One flush a connection an emit (ISSUE 41): how deep emit() is nested
  // (a self-delivered message emits from inside one), the connections the
  // running emit queued frames for, and the two tallies that show it:
  // frames handed to queue_bytes, send() calls made (folded at the scrape).
  int emit_depth_ = 0;
  std::vector<Conn*> touched_;
  int64_t frames_out_ = 0;
  int64_t send_calls_ = 0;
  // Gateway tier (ISSUE 10): live gateway links by id, and which link
  // forwarded for each client token. Routes are a bounded cache — on
  // overflow the map clears and un-routed "gw/" replies fall back to a
  // fan-out over ALL gateway links (gateways drop tokens they don't own),
  // so degradation is extra frames, never lost quorums.
  std::map<uint64_t, Conn*> gateway_links_;
  std::map<std::string, uint64_t> gateway_routes_;
  uint64_t gateway_link_seq_ = 0;
  // Multi-core front end (ISSUE 13): created in start() when
  // cfg_.net_threads > 1. In that mode this class owns NO data sockets —
  // gateway links live in their shards and are addressed here by the
  // packed (shard << 48 | conn token) keys below; gateway_routes_ maps
  // client tokens to those same keys.
  std::unique_ptr<NetShards> shards_;
  std::set<uint64_t> sharded_gateways_;
  // Last-seen shard counter snapshots: shard counters are absolute
  // relaxed atomics, prometheus counters are monotonic increments.
  int64_t seen_cross_wakes_ = 0;
  int64_t seen_shard_backpressure_ = 0;
  int64_t seen_shard_dropped_ = 0;
  int64_t shard_handoffs_ = 0;    // pbft_shard_handoff_seconds' count ...
  double shard_handoff_s_ = 0.0;  // ... and sum, for /status and --trace
  std::array<int64_t, kFrontStages> seen_shard_us_{};
  std::array<int64_t, kFrontStages> seen_pipe_us_{};
  int64_t seen_shard_chaos_ = 0;
  int64_t seen_shard_encodes_ = 0;
  int64_t gateway_forwarded_ = 0;  // requests received over gateway links
  // Perf-under-faults surface (ISSUE 12): explicit admission rejections
  // and live gateway links lost mid-run (their clients must fail over).
  int64_t overload_rejections_ = 0;
  int64_t gateway_failovers_ = 0;
  // Observe the backoff level into the gauge + flight ring when it
  // changes (the chaos bench's storm signal).
  void observe_backoff_level();
  int64_t batches_run_ = 0;
  int64_t frames_in_ = 0;
  // Serialize-once accounting (metrics_json + the counter-based invariant
  // test): encodes must track broadcasts, never broadcasts x peers.
  int64_t broadcasts_ = 0;
  int64_t broadcast_encodes_ = 0;
  // Bounded verify accumulation (ClusterConfig::verify_flush_us): the
  // window opens when the first item queues and flushes at the item
  // target or the deadline, whichever comes first. poll_once clamps its
  // poll timeout to the deadline so a quiet socket can't stretch the
  // promised latency bound.
  bool verify_window_open_ = false;
  std::chrono::steady_clock::time_point verify_window_start_{};
  // Open request-batch window on the primary (ISSUE 4): opens when the
  // first request joins the open batch, seals at batch_max_items (inside
  // the replica) or at the batch_flush_us deadline (here).
  bool batch_window_open_ = false;
  std::chrono::steady_clock::time_point batch_window_start_{};
  // Arrival of the OLDEST request in the primary's open batch
  // (trace_request_rx stamps it when a request finds the batch empty: one
  // clock read a batch, none a message; NaN = no batch open). The seal
  // (on_phase "request") observes pbft_request_wait_seconds against it
  // and leaves the wait here for trace_batch_sealed's wait_s. A refused
  // seal leaves the batch open and the stamp as it is.
  double batch_oldest_at_ = std::nan("");
  double pending_batch_wait_s_ = 0.0;
  // Tentative mode: seq -> its "executed" stamp, until the committed
  // floor passes it (on_commit_floor). A rolled-back sequence number is
  // stamped again when it re-executes; entries below the floor go with it.
  std::map<int64_t, double> tentative_exec_at_;
  // Last-seen replica counters, for the executed/rounds metric deltas.
  int64_t seen_executed_ = 0;
  int64_t seen_rounds_ = 0;
  // Async verify launch in flight (RemoteVerifier): the event loop keeps
  // draining peers while the service runs the launch — the next window
  // accumulates during the round-trip instead of the loop stalling on it.
  bool verify_inflight_ = false;
  std::vector<VerifyItem> inflight_items_;
  std::chrono::steady_clock::time_point inflight_start_{};
  // One span of verdicts in hand and not yet applied: read by
  // finish_verify_async (or made by the safety net) in this pass, applied
  // at its end behind the launch of the next span. read_at less
  // dispatched_at is pbft_verify_seconds' reading.
  struct KeptVerdicts {
    std::chrono::steady_clock::time_point dispatched_at, read_at;
    bool launched_ahead;
    std::vector<uint8_t> verdicts;
  };
  std::optional<KeptVerdicts> kept_;
  int64_t launched_ahead_ = 0;  // launches made while a span was kept
  // Kept spans worked through, and the seconds from their delivery
  // beginning to its last send (pbft_verdict_apply_seconds; /status
  // verify_apply).
  int64_t verdict_applies_ = 0;
  double verdict_apply_s_ = 0.0;
  // pbft_verify_inbox_wait_seconds: since when an entry no launch has
  // taken (Replica::unlaunched_count) has been waiting.
  bool inbox_waiting_ = false;
  std::chrono::steady_clock::time_point inbox_since_{};
  int verify_deadline_ms_ = 15000;
  int64_t verify_deadline_fired_ = 0;  // surfaced in metrics_json
  // Batches the CPU safety net verified HERE because the remote launch
  // failed or overran its deadline. verify_service_fallbacks() adds the
  // verifier's own host fallbacks (service warming/unreachable/killed).
  int64_t safety_net_batches_ = 0;
  int64_t fallbacks_reported_ = 0;  // already fed to the counter metric
  int64_t verify_service_fallbacks() const;

  // Health-document progress tracker (ISSUE 16): the executed_upto we
  // last saw move and when we saw it. Updated by refresh_health(), so
  // last_progress_seconds is quantized to the observation cadence — fine
  // for a detector whose threshold is whole seconds.
  std::chrono::steady_clock::time_point start_time_ =
      std::chrono::steady_clock::now();
  int64_t progress_seen_executed_ = -1;
  std::chrono::steady_clock::time_point progress_seen_at_ =
      std::chrono::steady_clock::now();

  // Metrics registry + scrape listener (enabled by set_metrics_port).
  Metrics metrics_;
  // The loop thread's stage clock and what fold_counters has already fed
  // to the registry (microseconds a stage; passes, frames in and out, MAC
  // frames, gateway requests and send() calls as the integers above count
  // them).
  LoopClock loop_clock_;
  std::array<int64_t, kLoopStages> seen_loop_us_{};
  int64_t seen_loop_total_us_ = 0;
  int64_t seen_wakeups_ = 0;
  int64_t seen_frames_in_ = 0;
  int64_t seen_mac_frames_ = 0;
  int64_t seen_gateway_forwarded_ = 0;
  int64_t seen_frames_out_ = 0;
  int64_t seen_send_calls_ = 0;
  int metrics_port_ = -1;
  int metrics_listen_fd_ = -1;
  int metrics_listen_port_ = 0;
  // Open consensus-phase spans, (view, seq) -> stamps[PHASES] (NaN =
  // phase not seen). Bounded: slots that never execute (abandoned view)
  // are evicted oldest-first past kMaxOpenSpans.
  std::map<std::pair<int64_t, int64_t>, std::array<double, 4>> open_spans_;
};

// "host:port" -> connected TCP fd (blocking connect), or -1.
int dial_tcp(const std::string& host_port);

// Nonblocking dial: returns the fd (or -1 on immediate failure) and sets
// *in_progress when the connect is still completing (EINPROGRESS) — the
// caller polls for POLLOUT and checks SO_ERROR.
int dial_tcp_nb(const std::string& host_port, bool* in_progress);

}  // namespace pbft
