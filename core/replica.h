// The C++ PBFT replica: deterministic, I/O-free state machine.
//
// Semantically identical to pbft_tpu/consensus/replica.py (both are
// original designs for this framework; cross-checked by the Python<->C++
// cluster equivalence tests). Fills in what the reference stubbed:
// 2f/2f+1 quorums (reference src/behavior.rs:181,:208,:222), (v,n)-keyed
// commit log (src/state.rs:23), watermarks + checkpoints
// (src/behavior.rs:154,:192), in-order execution with per-client
// exactly-once timestamps (src/behavior.rs:391-398), and batched signature
// gating via pending_items()/deliver_verdicts(): pending_items() cuts a
// SPAN off the verify inbox (every entry no earlier call took), and
// deliver_verdicts() applies one span's verdicts, front first. Several
// spans may be cut before the first is delivered — the runtime ships the
// span behind a batch before it works through that batch's verdicts —
// and they are delivered in the order they were cut.
#pragma once

#include <cstdint>
#include <deque>
#include <functional>
#include <map>
#include <optional>
#include <set>
#include <string>
#include <utility>
#include <vector>

#include "messages.h"
#include "verifier.h"
#include "wal.h"

namespace pbft {

// Forwarded-request retention bound (ISSUE 12, mirrors
// consensus/replica.py MAX_FORWARDED_RETAINED; constants lint): a backup
// remembers the last request it forwarded per client so a view change
// can RE-AIM it at the new primary — without this, a request forwarded
// to a primary that then gets voted out evaporates with the old view,
// and until the client's retransmission timer fires the request timers
// keep escalating view changes with nothing to order. On overflow the
// map clears: retransmission covers the forgotten entries.
inline constexpr size_t kMaxForwardedRetained = 1024;

struct ReplicaIdentity {
  int64_t replica_id = 0;
  std::string host;
  int port = 0;
  uint8_t pubkey[32] = {0};
};

struct ClusterConfig {
  std::vector<ReplicaIdentity> replicas;
  int64_t watermark_window = 256;
  int64_t checkpoint_interval = 16;
  int64_t batch_pad = 64;
  // Bounded verify accumulation (BASELINE north-star lever): when
  // verify_flush_us > 0, a replica holds its verify queue until
  // verify_flush_items are pending (0 = batch_pad) or the oldest item has
  // waited verify_flush_us — trading that much latency for a fatter
  // batching window (more items per verifier launch). 0 = flush every
  // event-loop pass (the original behavior).
  int64_t verify_flush_us = 0;
  int64_t verify_flush_items = 0;
  // Request batching (ISSUE 4): the primary accumulates client requests
  // into an ordered batch and runs ONE three-phase instance per batch.
  // batch_max_items caps the batch (1 = the pre-batching protocol,
  // wire-compatible with 1.1.0 peers); batch_flush_us bounds how long a
  // partial batch waits before the runtime seals it (0 = next event-loop
  // pass). Backups ignore both: acceptance is size-agnostic.
  int64_t batch_max_items = 1;
  int64_t batch_flush_us = 0;
  // Admission control (ISSUE 12, mirrors pbft_tpu/consensus/config.py):
  // admission_inflight caps one client's estimated in-flight requests
  // (its request timestamp's distance past the last executed one);
  // admission_backlog watermarks the replica's own backlog (verify inbox
  // + sealed-but-unexecuted sequences). A fresh request past either
  // bound is answered with an explicit {"type": "overloaded"} line and
  // dropped; retransmissions always pass. 0 disables either check.
  int64_t admission_inflight = 0;
  int64_t admission_backlog = 0;
  // Multi-core replica core (ISSUE 13): the number of event-loop shard
  // threads (each with a companion crypto pipeline thread) the native
  // runtime runs. 1 = the classic single-threaded loop. The asyncio
  // runtime accepts the key and stays single-loop (it logs as much);
  // the default is constants-linted against consensus/config.py.
  int64_t net_threads = 1;
  // Fast-path modes (ISSUE 14, protocol 1.3.0; defaults constants-linted
  // against consensus/config.py). fastpath = "mac" offers the per-link
  // MAC-vector authenticator mode in hellos (normal-case frames on
  // mutually-offering links skip hot-path signature verification);
  // tentative = true executes + replies at PREPARED with rollback on
  // view change (clients accept 2f+1 matching tentative votes).
  std::string fastpath = "sig";
  bool tentative = false;
  // Durable replica recovery (ISSUE 15; defaults constants-linted
  // against consensus/config.py): a non-empty wal_dir gives each
  // replica a write-ahead log at {wal_dir}/replica-{id}.wal (view, sent
  // votes, stable checkpoint + snapshot), group-commit flushed at the
  // emit boundary and replayed on restart so a kill -9'd replica
  // re-joins the SAME view without contradicting a persisted vote.
  // wal_fsync=false keeps the writes but skips the fsync.
  std::string wal_dir = "";
  bool wal_fsync = true;
  std::string verifier = "cpu";  // "cpu" | "host:port" | "/unix/path"
  // Encrypted replica-replica links (core/secure.cc; the reference's
  // development_transport bundles Noise on every link, src/main.rs:42).
  bool secure = false;

  int64_t n() const { return (int64_t)replicas.size(); }
  int64_t f() const { return (n() - 1) / 3; }
  int64_t primary_of(int64_t view) const { return view % n(); }

  static std::optional<ClusterConfig> from_json_text(const std::string& text);
};

// Outputs of the state machine.
struct ActionSend {
  int64_t dest;
  Message msg;
};
struct ActionBroadcast {
  Message msg;
};
struct ActionReply {
  std::string client;
  ClientReply msg;
};

struct Actions {
  std::vector<ActionSend> sends;
  std::vector<ActionBroadcast> broadcasts;
  std::vector<ActionReply> replies;

  void merge(Actions&& other);
};

class Replica {
 public:
  Replica(ClusterConfig config, int64_t replica_id, const uint8_t seed[32]);

  bool is_primary() const { return config_.primary_of(view_) == id_; }
  int64_t primary() const { return config_.primary_of(view_); }
  int64_t high_mark() const { return low_mark_ + config_.watermark_window; }
  int64_t executed_upto() const { return executed_upto_; }
  int64_t low_mark() const { return low_mark_; }
  std::string state_digest_hex() const { return to_hex(state_digest_, 32); }

  // Client request path (unauthenticated, like the reference's client
  // contract); backups forward to the primary. On the primary the
  // request joins the OPEN batch; the batch seals (one pre-prepare, one
  // sequence number for the whole batch) when batch_max_items is
  // reached — or when the runtime's batch_flush_us timer calls
  // flush_open_batch on a partial batch.
  Actions on_client_request(const ClientRequest& req);
  size_t open_batch_size() const { return open_batch_.size(); }
  Actions flush_open_batch();

  // Replica-to-replica: queue for batched signature verification. The
  // net layer passes the signable digest it derived from the received
  // frame bytes (messages.h message_signable_from_payload) so
  // pending_items never re-serializes; the digest-less overload (self
  // delivery, tests) computes it there instead.
  Actions receive(const Message& msg);
  Actions receive(const Message& msg, const uint8_t signable[32]);
  // Dispatch a message the net layer already authenticated via its
  // per-link session MAC (ISSUE 14 authenticator mode): no verify
  // queue, no signature check — the caller proved the sender and
  // checked the claimed replica id against the link's peer.
  Actions receive_authenticated(const Message& msg);
  // Cut the next span: one item per entry that awaits a verdict, from the
  // first entry no earlier call took to the inbox's end.
  std::vector<VerifyItem> pending_items();
  // Queue depths without building the items: the whole inbox, and the
  // entries no span has taken yet — the event loop's launch decision and
  // its bounded accumulation (verify_flush_us) check the latter every pass.
  size_t pending_count() const { return inbox_.size(); }
  size_t unlaunched_count() const { return inbox_.size() - inbox_taken_; }
  // Signatures this replica has made (pbft_signs_total).
  int64_t signs() const { return signs_; }
  // Apply the verdicts of the OLDEST undelivered span, in arrival order.
  // Stops at the first entry that still awaits a verdict, be it in a
  // later span (on the wire) or in none.
  Actions deliver_verdicts(const std::vector<uint8_t>& verdicts);

  // View change (PBFT §4.4): called by the runtime when its request timer
  // for the current primary expires. new_view < 0 means "next view".
  Actions start_view_change(int64_t new_view = -1);
  // Re-broadcast the pending VIEW-CHANGE verbatim (runtime retransmission
  // timer, ISSUE 12): under link loss this converges in the SAME view
  // where escalating would burn a view number per lost frame. No counter
  // moves, nothing is re-signed. Empty when no view change pends.
  Actions retransmit_view_change();
  bool in_view_change() const { return in_view_change_; }
  int64_t view() const { return view_; }
  // Admission-control inputs (ISSUE 12, read by the net layer): the
  // client's last EXECUTED timestamp (0 = never seen) and the count of
  // sealed-but-unexecuted sequences on this replica.
  int64_t client_last_timestamp(const std::string& client) const {
    auto it = last_timestamp_.find(client);
    return it == last_timestamp_.end() ? 0 : it->second;
  }
  int64_t seal_backlog() const {
    return seq_counter_ > executed_upto_ ? seq_counter_ - executed_upto_ : 0;
  }
  // Tentative execution surface (ISSUE 14, §5.3): the committed floor
  // (everything at or below it is committed-local AND executed; the
  // suffix above ran tentatively and can roll back), the chain digest
  // AT that floor, and what the view timer should treat as progress
  // (committed sequences in tentative mode — tentative executions roll
  // back and must not placate the timer while commits starve).
  int64_t committed_upto() const { return committed_upto_; }
  std::string committed_chain_hex() const {
    return to_hex(committed_chain_, 32);
  }
  int64_t progress_marker() const {
    return config_.tentative ? committed_upto_ : executed_upto_;
  }
  // True when accepted pre-prepares (or committed-but-unexecuted slots)
  // sit above executed_upto — the net layer's request-timer signal.
  bool has_unexecuted() const;

  // Metrics (SURVEY.md §5: first-class counters, not printf).
  std::map<std::string, int64_t> counters;

  // Consensus-phase observer (mirrors pbft_tpu/consensus/replica.py
  // phase_hook): called as hook(phase, view, seq) at each protocol
  // transition — "request" (primary sequence assignment), "pre_prepare",
  // "prepared", "committed", "executed". The state machine stays
  // clock-free; the net layer stamps transitions into spans
  // (net.cc on_phase -> Metrics histograms + consensus_span trace
  // events). Unset costs one bool check per transition.
  std::function<void(const char*, int64_t, int64_t)> phase_hook;

  // Batch-size observer: called with pp.requests.size() at every
  // pre-prepare accept (feeds the pbft_batch_size histogram). Unset
  // costs one bool check per accept.
  std::function<void(int64_t)> batch_hook;

  // Committed-floor observer (ISSUE 32, mirrors the Python replica's
  // commit_hook): called with each sequence number the committed floor
  // passes in note_committed, i.e. in tentative mode only. The net layer
  // sets the time against the sequence number's "executed" stamp
  // (pbft_tentative_commit_lag_seconds). NOT a phase: a "committed" stamp
  // after "executed" would break the phase-order invariant. Unset costs
  // one bool check per sequence number.
  std::function<void(int64_t)> commit_hook;

  // View-change observer (ISSUE 9, mirrors the Python replica's
  // view_hook): hook("view_change_sent", pending_view) when this replica
  // broadcasts VIEW-CHANGE, hook("new_view_installed", view) when it
  // enters the new view. Rare events; the net layer stamps them into
  // trace events + the flight recorder. Unset costs one bool check.
  std::function<void(const char*, int64_t)> view_hook;

  // Optional stateful-app hooks (PBFT §5.3 state transfer). Defaults keep
  // the reference's no-op app ("awesome!", reference src/message.rs:70)
  // with an empty snapshot. A stateful app sets all three; its snapshot is
  // embedded in the checkpoint payload that the 2f+1-certified checkpoint
  // digest commits to, and restored on state transfer.
  std::function<std::string(const std::string&, int64_t)> app_execute;
  std::function<std::string()> app_snapshot;
  std::function<void(const std::string&)> app_restore;

  // State transfer status + runtime retry hook (net layer re-broadcasts
  // the request on its progress timer instead of starting a view change).
  bool awaiting_state() const { return awaiting_state_.has_value(); }
  Actions retry_state_transfer();

  // Write-ahead log (ISSUE 15, core/wal.{h,cc}): when set, every vote
  // this replica sends is recorded (durable before the send — the net
  // layer flushes at its emit boundary) and a vote contradicting a
  // persisted one is refused. nullptr = the pre-durability behavior.
  void set_wal(Wal* w) { wal_ = w; }
  // Crash-recovery: reinstall the durable state a previous life
  // persisted (stable checkpoint wholesale + the view floor) BEFORE
  // networking starts; the suffix catches up via §5.3 state transfer.
  // False when the persisted checkpoint payload fails to parse.
  bool restore_from_wal(const WalState& state);

 private:
  using Key = std::pair<int64_t, int64_t>;  // (view, seq)

  template <typename M>
  M sign(M msg) const;
  mutable int64_t signs_ = 0;  // signatures made; no clock, no I/O

  Actions seal_batch();
  Actions dispatch(const Message& msg);
  void pop_inbox_front();  // keeps inbox_taken_ in step
  Actions on_pre_prepare(const PrePrepare& pp);
  Actions accept_pre_prepare(const PrePrepare& pp);
  Actions on_prepare(const Prepare& p);
  Actions insert_prepare(const Prepare& p);
  Actions maybe_commit(const Key& key);
  Actions on_commit(const Commit& c);
  Actions insert_commit(const Commit& c);
  Actions maybe_execute(const Key& key);
  Actions drain_executions();
  Actions on_checkpoint(const Checkpoint& cp);
  Actions insert_checkpoint(const Checkpoint& cp);
  Actions advance_watermark(int64_t stable_seq,
                            const std::string& stable_digest);
  // Canonical checkpoint payload (byte-identical to the Python runtime's
  // Replica._checkpoint_payload) + the state-transfer handlers.
  std::string checkpoint_payload(int64_t seq) const;
  Actions on_state_request(const StateRequest& sr);
  Actions on_state_response(const StateResponse& resp);
  // Install a certified checkpoint payload wholesale (state transfer +
  // WAL recovery); false when it doesn't parse (nothing mutated).
  bool install_checkpoint_payload(int64_t seq, const std::string& snapshot);
  // Persist the stable checkpoint + adopted certificate when we hold
  // the payload (ISSUE 15); no-op without a wal.
  void wal_checkpoint(int64_t seq);

  // View change internals (mirrors pbft_tpu/consensus/replica.py; hot-path
  // signatures are batch-verified, rare view-change evidence inline).
  struct OEntry {
    int64_t seq;
    std::string digest;
    std::vector<ClientRequest> requests;  // empty -> empty (null) batch
  };
  bool verify_inline(int64_t rid, const Message& m,
                     const std::string& sig_hex) const;
  bool validate_view_change(const ViewChange& vc) const;
  Actions on_view_change(const ViewChange& vc);
  Actions on_new_view(const NewView& nv);
  Actions maybe_new_view(int64_t v);
  // stable_vc: the (validated) view-change whose checkpoint proof
  // certifies min_s — the digest AND the certificate are adopted on the
  // watermark jump (a stale proof would wedge future view changes).
  Actions enter_new_view(int64_t v, int64_t min_s,
                         const ViewChange* stable_vc,
                         const std::vector<PrePrepare>& pps);
  JsonArray prepared_proofs() const;
  std::pair<int64_t, std::vector<OEntry>> compute_o(
      const std::vector<ViewChange>& vcs) const;
  bool prepared(const Key& key) const;
  bool committed_local(const Key& key) const;
  bool in_window(int64_t seq) const {
    return low_mark_ < seq && seq <= high_mark();
  }

  ClusterConfig config_;
  int64_t id_;
  uint8_t seed_[32];
  Wal* wal_ = nullptr;  // not owned (ISSUE 15); nullptr = no durability
  int64_t view_ = 0;
  int64_t seq_counter_ = 0;
  int64_t low_mark_ = 0;
  int64_t executed_upto_ = 0;
  uint8_t state_digest_[32];
  // Tentative execution (ISSUE 14; mirrors consensus/replica.py): the
  // committed floor, the chain digest at it, per-sequence undo records
  // for the tentative suffix, sequences committed-local-and-executed
  // but not yet contiguous with the floor, and checkpoint payloads
  // captured at execution whose emission waits for the commit point.
  struct UndoItem {
    std::string client;
    bool had_ts = false;
    int64_t prev_ts = 0;
    bool had_reply = false;
    ClientReply prev_reply;
  };
  struct Undo {
    uint8_t chain[32] = {0};
    std::vector<UndoItem> items;
    bool have_app = false;
    std::string app_snapshot;
  };
  int64_t committed_upto_ = 0;
  uint8_t committed_chain_[32];
  std::map<int64_t, Undo> tentative_undo_;
  std::set<int64_t> committed_seqs_;
  std::map<int64_t, std::string> pending_checkpoints_;
  Actions note_committed(int64_t seq);
  void rollback_tentative();

  std::map<Key, PrePrepare> pre_prepares_;
  std::map<Key, std::map<int64_t, Prepare>> prepares_;
  std::map<Key, std::map<int64_t, Commit>> commits_;
  std::set<Key> sent_commit_;
  std::map<int64_t, std::pair<int64_t, std::string>> pending_execution_;
  std::map<std::string, int64_t> last_timestamp_;
  std::map<std::string, ClientReply> last_reply_;
  std::map<int64_t, std::map<int64_t, Checkpoint>> checkpoints_;
  // The primary's open (unsealed) batch + the highest pending timestamp
  // per client, so duplicate suppression sees unsealed requests too.
  std::vector<ClientRequest> open_batch_;
  std::map<std::string, int64_t> open_batch_ts_;
  // Last request forwarded to the primary, per client (backup role;
  // ISSUE 12): re-aimed at the new primary on view entry, retired at
  // execution. Bounded by kMaxForwardedRetained.
  std::map<std::string, ClientRequest> forwarded_;
  // Highest timestamp per client SEALED under a sequence in the current
  // view (primary duplicate check between seal and execution; cleared on
  // view entry so abandoned-view requests stay re-orderable).
  std::map<std::string, int64_t> sealed_ts_;
  struct InboxEntry {
    Message msg;
    bool has_signable = false;
    // MAC-accepted frame queued behind unverified signed types purely
    // for ordering (ISSUE 14): passes without consuming a verdict.
    bool pre_authenticated = false;
    uint8_t signable[32];
  };
  std::deque<InboxEntry> inbox_;
  // Entries at the inbox's front that pending_items() has cut into spans:
  // their verdicts are in the runtime's hands or on the wire. Behind them,
  // entries no launch has taken.
  size_t inbox_taken_ = 0;
  // Checkpoint payloads we can serve to lagging peers, and the
  // (seq, digest) we are ourselves waiting to fetch after a watermark jump.
  std::map<int64_t, std::string> snapshots_;
  std::optional<std::pair<int64_t, std::string>> awaiting_state_;

  bool in_view_change_ = false;
  int64_t pending_view_ = 0;
  std::map<int64_t, std::map<int64_t, ViewChange>> view_changes_;
  // NEW-VIEW messages this replica (as primary-elect) already built,
  // keyed by view (ISSUE 12): membership suppresses redundant
  // recomputation, and the cached message is RESENT point-to-point to a
  // replica whose retransmitted VIEW-CHANGE shows it missed the
  // broadcast. Pruned to views >= current on view entry.
  std::map<int64_t, NewView> new_view_sent_;
  // Our own latest VIEW-CHANGE (pending view) for the runtime's
  // retransmission timer; cleared on view entry.
  std::optional<ViewChange> my_view_change_;
  JsonArray stable_proof_;  // 2f+1 checkpoint dicts @ low_mark (C)
};

}  // namespace pbft
