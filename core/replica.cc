#include "replica.h"

#include <cstring>

#include "blake2b.h"
#include "ed25519.h"

namespace pbft {

void Actions::merge(Actions&& other) {
  for (auto& s : other.sends) sends.push_back(std::move(s));
  for (auto& b : other.broadcasts) broadcasts.push_back(std::move(b));
  for (auto& r : other.replies) replies.push_back(std::move(r));
}

std::optional<ClusterConfig> ClusterConfig::from_json_text(
    const std::string& text) {
  auto j = Json::parse(text);
  if (!j || !j->is_object()) return std::nullopt;
  ClusterConfig cfg;
  if (const Json* v = j->find("watermark_window")) cfg.watermark_window = v->as_int();
  if (const Json* v = j->find("checkpoint_interval"))
    cfg.checkpoint_interval = v->as_int();
  if (const Json* v = j->find("batch_pad")) cfg.batch_pad = v->as_int();
  if (const Json* v = j->find("verify_flush_us"))
    cfg.verify_flush_us = v->as_int();
  if (const Json* v = j->find("verify_flush_items"))
    cfg.verify_flush_items = v->as_int();
  if (const Json* v = j->find("batch_max_items"))
    cfg.batch_max_items = v->as_int();
  if (const Json* v = j->find("batch_flush_us"))
    cfg.batch_flush_us = v->as_int();
  if (const Json* v = j->find("admission_inflight"))
    cfg.admission_inflight = v->as_int();
  if (const Json* v = j->find("admission_backlog"))
    cfg.admission_backlog = v->as_int();
  if (const Json* v = j->find("net_threads")) cfg.net_threads = v->as_int();
  if (const Json* v = j->find("fastpath"); v && v->is_string())
    cfg.fastpath = v->as_string();
  if (const Json* v = j->find("tentative")) cfg.tentative = v->as_bool();
  if (const Json* v = j->find("wal_dir"); v && v->is_string())
    cfg.wal_dir = v->as_string();
  if (const Json* v = j->find("wal_fsync")) cfg.wal_fsync = v->as_bool();
  if (const Json* v = j->find("verifier"); v && v->is_string())
    cfg.verifier = v->as_string();
  if (const Json* v = j->find("secure")) cfg.secure = v->as_bool();
  const Json* reps = j->find("replicas");
  if (!reps || !reps->is_array()) return std::nullopt;
  for (const Json& r : reps->as_array()) {
    ReplicaIdentity id;
    const Json* rid = r.find("replica_id");
    const Json* host = r.find("host");
    const Json* port = r.find("port");
    const Json* pk = r.find("pubkey");
    if (!rid || !host || !port || !pk) return std::nullopt;
    id.replica_id = rid->as_int();
    id.host = host->as_string();
    id.port = (int)port->as_int();
    if (!from_hex(pk->as_string(), id.pubkey, 32)) return std::nullopt;
    cfg.replicas.push_back(std::move(id));
  }
  return cfg;
}

Replica::Replica(ClusterConfig config, int64_t replica_id,
                 const uint8_t seed[32])
    : config_(std::move(config)), id_(replica_id) {
  std::memcpy(seed_, seed, 32);
  static const char* kGenesis = "pbft-genesis";
  blake2b_256(state_digest_, (const uint8_t*)kGenesis, std::strlen(kGenesis));
  std::memcpy(committed_chain_, state_digest_, 32);
  for (const char* name :
       {"sig_verified", "sig_rejected", "mac_verified",
        "tentative_executions", "tentative_rollbacks",
        "seals_refused", "inline_verifies",
        "pre_prepares_accepted", "prepares_accepted", "commits_accepted",
        "executed", "rounds_executed", "duplicate_requests",
        "checkpoints_stable", "state_transfers"}) {
    counters[name] = 0;
  }
}

template <typename M>
M Replica::sign(M msg) const {
  uint8_t digest[32], sig[64];
  message_signable(Message(msg), digest);
  ed25519_sign(sig, seed_, digest, 32);
  ++signs_;
  msg.sig = to_hex(sig, 64);
  return msg;
}

Actions Replica::on_client_request(const ClientRequest& req) {
  Actions out;
  // §4.1: EVERY replica remembers the last reply it sent each client and
  // re-sends it on a retransmission of an executed request — backups
  // included, BEFORE the forward-to-primary. The cached reply carries
  // this replica's own signature, so f+1 retransmission answers form a
  // distinct-voter quorum (the gateway fan-back depends on this: routing
  // every duplicate's answer through the primary alone can never
  // convince a client that f+1 replicas executed).
  auto cached = last_reply_.find(req.client);
  if (cached != last_reply_.end() &&
      cached->second.timestamp == req.timestamp) {
    counters["duplicate_requests"] += 1;
    out.replies.push_back({req.client, cached->second});
    return out;
  }
  // A timestamp at or below the client's last EXECUTED one can never
  // execute again (per-client exactly-once) and its reply is no longer
  // cached: drop it on EVERY role (ISSUE 12). Backups used to forward
  // these forever — each forward re-armed the request timer for a
  // request with nothing left to order, and a client stuck
  // retransmitting a superseded timestamp could drive perpetual view
  // changes out of pure duplicate traffic.
  {
    auto it = last_timestamp_.find(req.client);
    if (it != last_timestamp_.end() && req.timestamp <= it->second) {
      counters["duplicate_requests"] += 1;
      return out;
    }
  }
  if (!is_primary()) {
    // Forward to the primary, and REMEMBER the request: if this view
    // dies before it executes, enter_new_view re-aims it at the new
    // primary (ISSUE 12 — see kMaxForwardedRetained).
    if (forwarded_.size() >= kMaxForwardedRetained) forwarded_.clear();
    forwarded_[req.client] = req;
    out.sends.push_back({primary(), Message(req)});
    return out;
  }
  // Duplicate suppression must also see the OPEN batch: a retransmission
  // of a request still waiting unsealed must not burn a second slot.
  auto pending = open_batch_ts_.find(req.client);
  if (pending != open_batch_ts_.end() && req.timestamp <= pending->second) {
    counters["duplicate_requests"] += 1;
    return out;
  }
  // Already SEALED under a sequence in this view (PBFT §4.2: the primary
  // checks its log): a retransmission arriving between seal and execution
  // must not burn a second three-phase instance. Cleared on view entry —
  // a request sealed in an abandoned view may need re-ordering.
  auto sealed = sealed_ts_.find(req.client);
  if (sealed != sealed_ts_.end() && req.timestamp <= sealed->second) {
    counters["duplicate_requests"] += 1;
    return out;
  }
  open_batch_.push_back(req);
  open_batch_ts_[req.client] = req.timestamp;
  if ((int64_t)open_batch_.size() >= std::max<int64_t>(1, config_.batch_max_items)) {
    return seal_batch();
  }
  return out;  // the runtime's batch_flush_us timer seals partials
}

Actions Replica::flush_open_batch() {
  if (open_batch_.empty()) return {};
  return seal_batch();
}

Actions Replica::seal_batch() {
  if (seq_counter_ + 1 > high_mark()) {
    counters["seals_refused"] += 1;
    return {};  // window closed: stay open
  }
  if (wal_ != nullptr &&
      !wal_->note_vote(kWalVotePrePrepare, view_, seq_counter_ + 1,
                       batch_digest_hex(open_batch_))) {
    // A durable pre-prepare for this (view, seq) names a DIFFERENT
    // batch: sealing would equivocate. Leave the batch open; the
    // watermark / view machinery resolves the slot.
    return {};
  }
  std::vector<ClientRequest> batch;
  batch.swap(open_batch_);
  open_batch_ts_.clear();
  for (const auto& req : batch) sealed_ts_[req.client] = req.timestamp;
  seq_counter_ += 1;
  if (phase_hook) phase_hook("request", view_, seq_counter_);
  PrePrepare pp;
  pp.view = view_;
  pp.seq = seq_counter_;
  pp.requests = std::move(batch);
  pp.digest = pp.batch_digest();
  pp.replica = id_;
  pp = sign(pp);
  Actions out;
  out.broadcasts.push_back({Message(pp)});
  out.merge(accept_pre_prepare(pp));
  return out;
}

Actions Replica::receive(const Message& msg) {
  if (std::holds_alternative<ClientRequest>(msg)) {
    return on_client_request(std::get<ClientRequest>(msg));
  }
  inbox_.push_back(InboxEntry{msg, false, false, {}});
  return {};
}

Actions Replica::receive(const Message& msg, const uint8_t signable[32]) {
  if (std::holds_alternative<ClientRequest>(msg)) {
    return on_client_request(std::get<ClientRequest>(msg));
  }
  InboxEntry e{msg, true, false, {}};
  std::memcpy(e.signable, signable, 32);
  inbox_.push_back(std::move(e));
  return {};
}

Actions Replica::receive_authenticated(const Message& msg) {
  counters["mac_verified"] += 1;
  if (std::holds_alternative<ClientRequest>(msg)) {
    return on_client_request(std::get<ClientRequest>(msg));
  }
  // ORDERING (ISSUE 14): when the verify inbox is non-empty the message
  // queues BEHIND it (pre-verified) instead of dispatching immediately —
  // a MAC frame overtaking a still-unverified NEW-VIEW from the same
  // sender would be dropped as belonging to a view this replica has not
  // entered yet, and the primary's per-view duplicate suppression then
  // pins the request until the NEXT view change (a liveness wedge the
  // chaos soak caught). The inbox only ever holds the rare signed types
  // in MAC mode, so the fast path stays fast.
  if (!inbox_.empty()) {
    InboxEntry e{msg, false, true, {}};
    inbox_.push_back(std::move(e));
    return {};
  }
  return dispatch(msg);
}

namespace {
int64_t replica_of(const Message& m) {
  if (auto* pp = std::get_if<PrePrepare>(&m)) return pp->replica;
  if (auto* p = std::get_if<Prepare>(&m)) return p->replica;
  if (auto* c = std::get_if<Commit>(&m)) return c->replica;
  if (auto* cp = std::get_if<Checkpoint>(&m)) return cp->replica;
  if (auto* vc = std::get_if<ViewChange>(&m)) return vc->replica;
  if (auto* nv = std::get_if<NewView>(&m)) return nv->replica;
  if (auto* sr = std::get_if<StateRequest>(&m)) return sr->replica;
  if (auto* sp = std::get_if<StateResponse>(&m)) return sp->replica;
  return -1;
}
const std::string* sig_of(const Message& m) {
  if (auto* pp = std::get_if<PrePrepare>(&m)) return &pp->sig;
  if (auto* p = std::get_if<Prepare>(&m)) return &p->sig;
  if (auto* c = std::get_if<Commit>(&m)) return &c->sig;
  if (auto* cp = std::get_if<Checkpoint>(&m)) return &cp->sig;
  if (auto* vc = std::get_if<ViewChange>(&m)) return &vc->sig;
  if (auto* nv = std::get_if<NewView>(&m)) return &nv->sig;
  if (auto* sr = std::get_if<StateRequest>(&m)) return &sr->sig;
  if (auto* sp = std::get_if<StateResponse>(&m)) return &sp->sig;
  return nullptr;
}
}  // namespace

std::vector<VerifyItem> Replica::pending_items() {
  std::vector<VerifyItem> items;
  items.reserve(inbox_.size() - inbox_taken_);
  for (auto it = inbox_.begin() + (std::ptrdiff_t)inbox_taken_;
       it != inbox_.end(); ++it) {
    const InboxEntry& e = *it;
    if (e.pre_authenticated) continue;  // passes without a verdict
    const Message& msg = e.msg;
    VerifyItem item{};
    int64_t rid = replica_of(msg);
    if (rid >= 0 && rid < config_.n()) {
      std::memcpy(item.pub, config_.replicas[rid].pubkey, 32);
    }
    if (e.has_signable) {
      // Receive-side canonical reuse: the net layer already hashed the
      // sender's framed bytes — no parse -> re-serialize -> hash here.
      std::memcpy(item.msg, e.signable, 32);
    } else {
      message_signable(msg, item.msg);
    }
    const std::string* sig = sig_of(msg);
    if (!sig || !from_hex(*sig, item.sig, 64)) {
      std::memset(item.sig, 0, 64);  // guaranteed invalid
    }
    items.push_back(item);
  }
  inbox_taken_ = inbox_.size();
  return items;
}

void Replica::pop_inbox_front() {
  inbox_.pop_front();
  if (inbox_taken_ > 0) --inbox_taken_;
}

Actions Replica::deliver_verdicts(const std::vector<uint8_t>& verdicts) {
  // Arrival order, with pre-authenticated (MAC-accepted) entries passing
  // for free — they queued behind the signed types purely for ordering
  // and were counted at receive; verification-needing entries consume
  // one verdict each, and trailing pre-authenticated entries drain
  // greedily once the verdicts run out: up to the first entry that still
  // awaits a verdict, whether a later span holds it (on the wire) or none
  // does. What dispatch queues for this replica itself goes to the back.
  Actions out;
  size_t vi = 0;
  while (!inbox_.empty()) {
    InboxEntry& front = inbox_.front();
    if (!front.pre_authenticated) {
      if (vi >= verdicts.size()) break;
      const bool ok = verdicts[vi] != 0;
      ++vi;
      counters[ok ? "sig_verified" : "sig_rejected"] += 1;
      if (!ok) {
        pop_inbox_front();
        continue;
      }
    }
    Message msg = std::move(front.msg);
    pop_inbox_front();
    out.merge(dispatch(msg));
  }
  return out;
}

Actions Replica::dispatch(const Message& msg) {
  if (auto* pp = std::get_if<PrePrepare>(&msg)) return on_pre_prepare(*pp);
  if (auto* p = std::get_if<Prepare>(&msg)) return on_prepare(*p);
  if (auto* c = std::get_if<Commit>(&msg)) return on_commit(*c);
  if (auto* cp = std::get_if<Checkpoint>(&msg)) return on_checkpoint(*cp);
  if (auto* vc = std::get_if<ViewChange>(&msg)) return on_view_change(*vc);
  if (auto* nv = std::get_if<NewView>(&msg)) return on_new_view(*nv);
  if (auto* sr = std::get_if<StateRequest>(&msg)) return on_state_request(*sr);
  if (auto* sp = std::get_if<StateResponse>(&msg))
    return on_state_response(*sp);
  if (auto* r = std::get_if<ClientRequest>(&msg)) return on_client_request(*r);
  return {};
}

Actions Replica::on_pre_prepare(const PrePrepare& pp) {
  if (in_view_change_) return {};  // §4.4: only cp/vc/nv accepted
  if (pp.view != view_ || pp.replica != primary()) return {};
  if (pp.batch_digest() != pp.digest) return {};
  if (!in_window(pp.seq)) return {};
  if (pre_prepares_.count({pp.view, pp.seq})) return {};
  return accept_pre_prepare(pp);
}

Actions Replica::accept_pre_prepare(const PrePrepare& pp) {
  Key key{pp.view, pp.seq};
  if (wal_ != nullptr) {
    // Amnesia guard (ISSUE 15): our durable vote for this slot — the
    // pre-prepare we sealed as primary, or the prepare we broadcast as
    // backup — is the floor a restart must honor. A pre-prepare naming
    // a different digest is refused outright; one naming the SAME
    // digest re-enters normally, which is how a recovered replica
    // resumes the round without re-voting anything new.
    const uint8_t kind = config_.primary_of(pp.view) == id_
                             ? kWalVotePrePrepare
                             : kWalVotePrepare;
    if (!wal_->note_vote(kind, pp.view, pp.seq, pp.digest)) return {};
  }
  pre_prepares_.emplace(key, pp);
  counters["pre_prepares_accepted"] += 1;
  if (phase_hook) phase_hook("pre_prepare", pp.view, pp.seq);
  if (batch_hook) batch_hook((int64_t)pp.requests.size());
  // The primary's pre-prepare stands in for its prepare (PBFT §4.2): only
  // backups multicast PREPARE, and prepared() wants 2f *backup* prepares,
  // giving 2f+1 distinct replicas per certificate.
  if (config_.primary_of(pp.view) == id_) return maybe_commit(key);
  Prepare prep;
  prep.view = pp.view;
  prep.seq = pp.seq;
  prep.digest = pp.digest;
  prep.replica = id_;
  prep = sign(prep);
  Actions out;
  out.broadcasts.push_back({Message(prep)});
  out.merge(insert_prepare(prep));
  return out;
}

Actions Replica::on_prepare(const Prepare& p) {
  if (in_view_change_ || p.view != view_ || !in_window(p.seq)) return {};
  return insert_prepare(p);
}

Actions Replica::insert_prepare(const Prepare& p) {
  Key key{p.view, p.seq};
  auto& slot = prepares_[key];
  if (slot.count(p.replica)) return {};
  slot.emplace(p.replica, p);
  counters["prepares_accepted"] += 1;
  return maybe_commit(key);
}

bool Replica::prepared(const Key& key) const {
  auto pp = pre_prepares_.find(key);
  if (pp == pre_prepares_.end()) return false;
  auto slot = prepares_.find(key);
  if (slot == prepares_.end()) return false;
  // 2f matching prepares from non-primary replicas + the primary's
  // pre-prepare = 2f+1 distinct members per certificate (PBFT §4.2's
  // quorum-intersection requirement; counting a primary prepare would
  // shrink certificates to 2f distinct replicas).
  const int64_t primary = config_.primary_of(key.first);
  int64_t matching = 0;
  for (const auto& [rid, p] : slot->second) {
    if (rid != primary && p.digest == pp->second.digest) matching += 1;
  }
  return matching >= 2 * config_.f();
}

Actions Replica::maybe_commit(const Key& key) {
  if (sent_commit_.count(key) || !prepared(key)) return {};
  if (wal_ != nullptr &&
      !wal_->note_vote(kWalVoteCommit, key.first, key.second,
                       pre_prepares_.at(key).digest)) {
    return {};  // contradicts a durable commit vote: never send
  }
  sent_commit_.insert(key);
  if (phase_hook) phase_hook("prepared", key.first, key.second);
  Commit cm;
  cm.view = key.first;
  cm.seq = key.second;
  cm.digest = pre_prepares_.at(key).digest;
  cm.replica = id_;
  cm = sign(cm);
  Actions out;
  out.broadcasts.push_back({Message(cm)});
  if (config_.tentative) {
    // Tentative execution (ISSUE 14, §5.3): PREPARED is the execute
    // point — the reply leaves one commit round-trip early, flagged
    // tentative; the commit quorum later promotes it (and a view change
    // before that rolls it back).
    if (key.second > executed_upto_ &&
        !pending_execution_.count(key.second)) {
      pending_execution_[key.second] = {key.first,
                                        pre_prepares_.at(key).digest};
      out.merge(drain_executions());
    }
  }
  out.merge(insert_commit(cm));
  return out;
}

Actions Replica::on_commit(const Commit& c) {
  if (in_view_change_ || c.view != view_ || !in_window(c.seq)) return {};
  return insert_commit(c);
}

Actions Replica::insert_commit(const Commit& c) {
  Key key{c.view, c.seq};
  auto& slot = commits_[key];
  if (slot.count(c.replica)) return {};
  slot.emplace(c.replica, c);
  counters["commits_accepted"] += 1;
  return maybe_execute(key);
}

bool Replica::committed_local(const Key& key) const {
  if (!prepared(key)) return false;
  auto pp = pre_prepares_.find(key);
  auto slot = commits_.find(key);
  if (slot == commits_.end()) return false;
  int64_t matching = 0;
  for (const auto& [rid, c] : slot->second) {
    if (c.digest == pp->second.digest) matching += 1;
  }
  return matching >= 2 * config_.f() + 1;
}

Actions Replica::maybe_execute(const Key& key) {
  if (!committed_local(key)) return {};
  int64_t seq = key.second;
  if (config_.tentative && seq <= executed_upto_) {
    // Already executed (tentatively) — the commit quorum arrived now:
    // advance the committed floor. No "committed" phase stamp: the span
    // closed at the tentative execution, and a committed stamp after
    // "executed" would violate the phase-order invariant.
    if (seq <= committed_upto_ || committed_seqs_.count(seq)) return {};
    return note_committed(seq);
  }
  if (seq <= executed_upto_ || pending_execution_.count(seq)) return {};
  pending_execution_[seq] = {key.first, pre_prepares_.at(key).digest};
  if (phase_hook) phase_hook("committed", key.first, seq);
  return drain_executions();
}

Actions Replica::drain_executions() {
  Actions out;
  while (pending_execution_.count(executed_upto_ + 1)) {
    int64_t seq = executed_upto_ + 1;
    auto [view, digest] = pending_execution_[seq];
    pending_execution_.erase(seq);
    // Tentative mode: is this execution already backed by a commit
    // quorum (definitive) or only by the prepared certificate
    // (tentative — reply flagged, undo recorded)?
    const bool tentative_mode = config_.tentative;
    const bool committed_now =
        !tentative_mode || committed_local({view, seq});
    Undo* undo = nullptr;
    if (tentative_mode) {
      // Undo record for EVERY executed sequence above the committed
      // floor (committed-now ones included — rollback walks the whole
      // suffix): prior chain digest, per-request prior exactly-once
      // entries, app snapshot when stateful.
      Undo u;
      std::memcpy(u.chain, state_digest_, 32);
      if (app_snapshot) {
        u.have_app = true;
        u.app_snapshot = app_snapshot();
      }
      undo = &tentative_undo_.emplace(seq, std::move(u)).first->second;
    }
    auto ppit = pre_prepares_.find({view, seq});
    if (ppit == pre_prepares_.end()) {
      executed_upto_ = seq;  // truncated past us; needs state transfer
      if (phase_hook) phase_hook("executed", view, seq);
      if (tentative_mode && committed_now) out.merge(note_committed(seq));
      continue;
    }
    const std::vector<ClientRequest>& batch = ppit->second.requests;
    executed_upto_ = seq;
    counters["rounds_executed"] += 1;
    if (phase_hook) phase_hook("executed", view, seq);
    auto null_fold = [&]() {
      // No-op execution (null request / empty batch): no reply, but the
      // sequence and state digest chain still advance — the SAME fold
      // for both encodings, so the gap-filler forms cannot diverge.
      std::vector<uint8_t> buf(state_digest_, state_digest_ + 32);
      static const char* kNull = "<null>";
      buf.insert(buf.end(), kNull, kNull + 6);
      for (int i = 7; i >= 0; --i) buf.push_back((uint8_t)(seq >> (8 * i)));
      blake2b_256(state_digest_, buf.data(), buf.size());
    };
    if (batch.empty()) null_fold();  // batched new-view gap filler
    for (const ClientRequest& req : batch) {
      if (req.client == "<null>") {
        // Legacy null request (a 1.1.0 peer's gap filler in a batch of 1).
        null_fold();
        continue;
      }
      auto it = last_timestamp_.find(req.client);
      if (it != last_timestamp_.end() && req.timestamp <= it->second) {
        // exactly-once, enforced per batch item in batch order
        counters["duplicate_requests"] += 1;
        continue;
      }
      if (undo != nullptr) {
        UndoItem item;
        item.client = req.client;
        if (it != last_timestamp_.end()) {
          item.had_ts = true;
          item.prev_ts = it->second;
        }
        auto rit = last_reply_.find(req.client);
        if (rit != last_reply_.end()) {
          item.had_reply = true;
          item.prev_reply = rit->second;
        }
        undo->items.push_back(std::move(item));
      }
      // Execution: the reference's app is a no-op returning "awesome!"
      // (reference src/message.rs:70); kept as the built-in default —
      // a stateful app overrides via the app_execute hook.
      std::string result =
          app_execute ? app_execute(req.operation, seq) : "awesome!";
      counters["executed"] += 1;
      {
        std::vector<uint8_t> buf(state_digest_, state_digest_ + 32);
        buf.insert(buf.end(), result.begin(), result.end());
        for (int i = 7; i >= 0; --i)
          buf.push_back((uint8_t)(seq >> (8 * i)));
        blake2b_256(state_digest_, buf.data(), buf.size());
      }
      last_timestamp_[req.client] = req.timestamp;
      forwarded_.erase(req.client);  // executed: retire the re-aim entry
      ClientReply reply;
      reply.view = view;
      reply.timestamp = req.timestamp;
      reply.client = req.client;
      reply.replica = id_;
      reply.result = result;
      reply.tentative = committed_now ? 0 : 1;
      reply = sign(reply);  // §4.1: a reply vote must prove its caster
      last_reply_[req.client] = reply;
      out.replies.push_back({req.client, reply});
    }
    if (seq % config_.checkpoint_interval == 0) {
      std::string payload = checkpoint_payload(seq);
      if (tentative_mode) {
        // Deferred emission: the payload is captured NOW (the state IS
        // the state at seq) but the Checkpoint message waits for the
        // commit point — a checkpoint may only ever cover state that
        // cannot roll back.
        pending_checkpoints_[seq] = std::move(payload);
      } else {
        snapshots_[seq] = payload;
        uint8_t d[32];
        blake2b_256(d, (const uint8_t*)payload.data(), payload.size());
        Checkpoint cp;
        cp.seq = seq;
        cp.digest = to_hex(d, 32);
        cp.replica = id_;
        cp = sign(cp);
        out.broadcasts.push_back({Message(cp)});
        out.merge(insert_checkpoint(cp));
      }
    }
    if (tentative_mode) {
      if (committed_now) {
        out.merge(note_committed(seq));
      } else {
        counters["tentative_executions"] += 1;
      }
    }
  }
  if (!config_.tentative) {
    // Signature mode: every execution is definitive — the floor tracks
    // execution so the progress/metrics surface is uniform.
    committed_upto_ = executed_upto_;
    std::memcpy(committed_chain_, state_digest_, 32);
  }
  return out;
}

// -- tentative promotion & rollback (ISSUE 14, §5.3) -------------------------

Actions Replica::note_committed(int64_t seq) {
  // Sequence `seq` is committed-local AND executed: advance the
  // committed floor over every contiguously-committed sequence, retire
  // their undo records, refresh committed_chain, and emit any
  // checkpoint whose (deferred) interval boundary the floor crossed.
  Actions out;
  if (seq <= committed_upto_) return out;
  committed_seqs_.insert(seq);
  while (committed_seqs_.count(committed_upto_ + 1)) {
    committed_upto_ += 1;
    const int64_t s = committed_upto_;
    committed_seqs_.erase(s);
    tentative_undo_.erase(s);
    if (commit_hook) commit_hook(s);
    auto pit = pending_checkpoints_.find(s);
    if (pit != pending_checkpoints_.end()) {
      std::string payload = std::move(pit->second);
      pending_checkpoints_.erase(pit);
      snapshots_[s] = payload;
      uint8_t d[32];
      blake2b_256(d, (const uint8_t*)payload.data(), payload.size());
      Checkpoint cp;
      cp.seq = s;
      cp.digest = to_hex(d, 32);
      cp.replica = id_;
      cp = sign(cp);
      out.broadcasts.push_back({Message(cp)});
      out.merge(insert_checkpoint(cp));
    }
  }
  auto nxt = tentative_undo_.find(committed_upto_ + 1);
  if (nxt != tentative_undo_.end()) {
    std::memcpy(committed_chain_, nxt->second.chain, 32);
  } else {
    std::memcpy(committed_chain_, state_digest_, 32);
  }
  return out;
}

void Replica::rollback_tentative() {
  // Undo every execution above the committed floor, newest first
  // (view-change entry, or a certified checkpoint past the floor):
  // chain digest, per-client exactly-once timestamps, cached replies,
  // and app state all revert to the committed point. Clients that
  // accepted a reply are safe regardless: 2f+1 matching tentative votes
  // imply f+1 honest replicas holding the full prepared certificate,
  // and any new-view quorum intersects them — the same batch is
  // re-issued at the same sequence.
  if (!config_.tentative || executed_upto_ <= committed_upto_) return;
  int64_t rolled = 0;
  for (int64_t seq = executed_upto_; seq > committed_upto_; --seq) {
    pending_checkpoints_.erase(seq);
    committed_seqs_.erase(seq);
    auto uit = tentative_undo_.find(seq);
    if (uit == tentative_undo_.end()) continue;  // defensive
    Undo& undo = uit->second;
    std::memcpy(state_digest_, undo.chain, 32);
    for (auto it = undo.items.rbegin(); it != undo.items.rend(); ++it) {
      if (it->had_ts) {
        last_timestamp_[it->client] = it->prev_ts;
      } else {
        last_timestamp_.erase(it->client);
      }
      if (it->had_reply) {
        last_reply_[it->client] = it->prev_reply;
      } else {
        last_reply_.erase(it->client);
      }
    }
    if (undo.have_app && app_restore) app_restore(undo.app_snapshot);
    tentative_undo_.erase(uit);
    rolled += 1;
  }
  executed_upto_ = committed_upto_;
  std::memcpy(committed_chain_, state_digest_, 32);
  for (auto it = pending_execution_.begin(); it != pending_execution_.end();) {
    it = it->first > committed_upto_ ? pending_execution_.erase(it)
                                    : std::next(it);
  }
  if (rolled) counters["tentative_rollbacks"] += rolled;
}

std::string Replica::checkpoint_payload(int64_t seq) const {
  // Canonical JSON the checkpoint digest commits to: app snapshot, the
  // execution chain digest, and the per-client exactly-once caches.
  // Byte-identical to Replica._checkpoint_payload in the Python runtime —
  // the digest gates state transfer across runtimes. The reply cache's
  // `replica` field is normalized to -1 so all correct replicas digest
  // identical bytes (the restorer stamps its own id back in).
  JsonObject o;
  o.emplace("app", app_snapshot ? app_snapshot() : std::string());
  o.emplace("chain", to_hex(state_digest_, 32));
  JsonArray replies;
  for (const auto& [client, reply] : last_reply_) {  // std::map: sorted
    Json rj = reply.to_json();
    rj.as_object()["replica"] = Json((int64_t)-1);
    rj.as_object()["sig"] = Json(std::string());  // replica-local too
    // Normalized away (mirrors replica.py): by emission time the prefix
    // is committed, and capture-time flag skew must not fork the bytes.
    rj.as_object().erase(kTentativeField);
    replies.push_back(Json(JsonArray{Json(client), std::move(rj)}));
  }
  o.emplace("replies", Json(std::move(replies)));
  o.emplace("seq", seq);
  JsonArray timestamps;
  for (const auto& [client, ts] : last_timestamp_) {
    timestamps.push_back(Json(JsonArray{Json(client), Json(ts)}));
  }
  o.emplace("timestamps", Json(std::move(timestamps)));
  return Json(std::move(o)).dump();
}

Actions Replica::on_state_request(const StateRequest& sr) {
  auto it = snapshots_.find(sr.seq);
  if (it == snapshots_.end() || sr.replica < 0 || sr.replica >= config_.n())
    return {};
  StateResponse resp;
  resp.seq = sr.seq;
  resp.snapshot = it->second;
  resp.replica = id_;
  resp = sign(resp);
  Actions out;
  out.sends.push_back({sr.replica, Message(resp)});
  return out;
}

Actions Replica::on_state_response(const StateResponse& resp) {
  if (!awaiting_state_ || resp.seq != awaiting_state_->first) return {};
  uint8_t d[32];
  blake2b_256(d, (const uint8_t*)resp.snapshot.data(), resp.snapshot.size());
  if (to_hex(d, 32) != awaiting_state_->second) return {};  // not certified
  if (!install_checkpoint_payload(resp.seq, resp.snapshot)) return {};
  awaiting_state_.reset();
  counters["state_transfers"] += 1;
  wal_checkpoint(resp.seq);
  return drain_executions();
}

bool Replica::install_checkpoint_payload(int64_t seq,
                                         const std::string& snapshot) {
  auto j = Json::parse(snapshot);
  if (!j || !j->is_object()) return false;
  const Json* app = j->find("app");
  const Json* chain = j->find("chain");
  const Json* replies = j->find("replies");
  const Json* timestamps = j->find("timestamps");
  if (!app || !app->is_string() || !chain || !chain->is_string() ||
      !replies || !replies->is_array() || !timestamps ||
      !timestamps->is_array())
    return {};
  uint8_t chain_bytes[32];
  if (!from_hex(chain->as_string(), chain_bytes, 32)) return {};
  std::map<std::string, ClientReply> new_replies;
  for (const Json& entry : replies->as_array()) {
    if (!entry.is_array() || entry.as_array().size() != 2) return {};
    const Json& client = entry.as_array()[0];
    auto msg = message_from_json(entry.as_array()[1]);
    if (!client.is_string() || !msg) return {};
    auto* reply = std::get_if<ClientReply>(&*msg);
    if (!reply) return {};
    ClientReply r = *reply;
    r.replica = id_;
    r = sign(r);  // a resent cached reply carries THIS replica's vote
    new_replies.emplace(client.as_string(), std::move(r));
  }
  std::map<std::string, int64_t> new_timestamps;
  for (const Json& entry : timestamps->as_array()) {
    if (!entry.is_array() || entry.as_array().size() != 2) return {};
    const Json& client = entry.as_array()[0];
    const Json& ts = entry.as_array()[1];
    if (!client.is_string() || !ts.is_int()) return {};
    new_timestamps.emplace(client.as_string(), ts.as_int());
  }
  if (app_restore) app_restore(app->as_string());
  std::memcpy(state_digest_, chain_bytes, 32);
  last_reply_ = std::move(new_replies);
  last_timestamp_ = std::move(new_timestamps);
  executed_upto_ = seq;
  // The installed state is 2f+1-certified: the committed floor moves
  // with it and any stale tentative bookkeeping dies here.
  committed_upto_ = seq;
  std::memcpy(committed_chain_, chain_bytes, 32);
  tentative_undo_.clear();
  committed_seqs_.clear();
  pending_checkpoints_.clear();
  snapshots_[seq] = snapshot;  // we can serve peers now
  return true;
}

bool Replica::restore_from_wal(const WalState& state) {
  // Crash-recovery (ISSUE 15; mirrors consensus/replica.py
  // restore_from_wal): reinstall the stable checkpoint wholesale, then
  // re-join the SAME view at that floor — the wal's vote log refuses
  // any send contradicting a pre-crash vote, and the suffix past the
  // checkpoint catches up through the ordinary protocol. A crash
  // mid-view-change re-joins at the OLD view (its VIEW-CHANGE vote, if
  // it got out, already counts; duplicates are ignored; a completed
  // change arrives as a NEW-VIEW for a higher view).
  bool ok = true;
  if (state.has_checkpoint) {
    if (install_checkpoint_payload(state.checkpoint_seq,
                                   state.checkpoint_payload)) {
      low_mark_ = state.checkpoint_seq;
      if (auto cert = Json::parse(state.checkpoint_cert);
          cert && cert->is_array()) {
        stable_proof_ = cert->as_array();
      }
      seq_counter_ = state.checkpoint_seq;
    } else {
      ok = false;  // start fresh: state transfer still covers it
    }
  }
  view_ = std::max(view_, state.view);
  // Never re-assign a sequence a previous life pre-prepared.
  seq_counter_ = std::max(seq_counter_, state.max_pre_prepare_seq());
  return ok;
}

Actions Replica::retry_state_transfer() {
  if (!awaiting_state_) return {};
  StateRequest sr;
  sr.seq = awaiting_state_->first;
  sr.replica = id_;
  sr = sign(sr);
  Actions out;
  out.broadcasts.push_back({Message(sr)});
  return out;
}

Actions Replica::on_checkpoint(const Checkpoint& cp) {
  if (cp.seq <= low_mark_) return {};
  return insert_checkpoint(cp);
}

Actions Replica::insert_checkpoint(const Checkpoint& cp) {
  // MAC mode (ISSUE 14): checkpoints were accepted by their link lane,
  // but their embedded signatures are what stable-checkpoint
  // CERTIFICATES are made of — admit only provable evidence, or one
  // sig-corrupting peer poisons every honest VIEW-CHANGE. Rare (one per
  // interval per replica): the inline verify is off the hot path.
  if (config_.fastpath == "mac") {
    counters["inline_verifies"] += 1;  // the normal case's one host check
    if (!verify_inline(cp.replica, Message(cp), cp.sig)) return {};
  }
  auto& slot = checkpoints_[cp.seq];
  if (slot.count(cp.replica)) return {};
  slot.emplace(cp.replica, cp);
  std::map<std::string, int64_t> by_digest;
  for (const auto& [rid, c] : slot) by_digest[c.digest] += 1;
  Actions out;
  for (const auto& [d, count] : by_digest) {
    if (count >= 2 * config_.f() + 1) {
      // Keep the 2f+1 matching checkpoint messages: they are the C
      // component of our next VIEW-CHANGE (PBFT §4.4).
      JsonArray proof;
      for (const auto& [rid, c] : slot) {
        if (c.digest == d) proof.push_back(c.to_json());
      }
      out.merge(advance_watermark(cp.seq, d));
      stable_proof_ = std::move(proof);
      wal_checkpoint(cp.seq);
      break;
    }
  }
  return out;
}

void Replica::wal_checkpoint(int64_t seq) {
  // Persist the stable checkpoint (ISSUE 15): payload (app snapshot +
  // reply cache) and the adopted 2f+1 certificate. Skipped when we
  // don't HOLD the payload yet (a lagging replica mid state transfer
  // records it when the StateResponse installs).
  if (wal_ == nullptr) return;
  auto it = snapshots_.find(seq);
  if (it == snapshots_.end()) return;
  wal_->note_checkpoint(seq, it->second, Json(stable_proof_).dump());
}

Actions Replica::advance_watermark(int64_t stable_seq,
                                   const std::string& stable_digest) {
  if (stable_seq <= low_mark_) return {};
  if (config_.tentative && stable_seq > committed_upto_) {
    // A 2f+1 quorum checkpointed past our committed floor: the
    // tentative suffix we hold may not match the certified chain —
    // revert to the committed point and catch up through the certified
    // state (the state-transfer branch below).
    rollback_tentative();
  }
  low_mark_ = stable_seq;
  counters["checkpoints_stable"] += 1;
  Actions out;
  if (stable_seq > executed_upto_) {
    // We missed executions that 2f+1 replicas checkpointed, and the
    // pruning below deletes the messages that would replay them: fetch
    // the certified checkpoint state from a peer (PBFT §5.3). Execution
    // stalls (executed_upto_ stays) until a StateResponse whose payload
    // hashes to stable_digest arrives; the net layer re-broadcasts the
    // request on its progress timer.
    awaiting_state_ = {stable_seq, stable_digest};
    StateRequest sr;
    sr.seq = stable_seq;
    sr.replica = id_;
    sr = sign(sr);
    out.broadcasts.push_back({Message(sr)});
  }
  auto prune_keys = [stable_seq](auto& log) {
    for (auto it = log.begin(); it != log.end();) {
      if (it->first.second <= stable_seq) it = log.erase(it);
      else ++it;
    }
  };
  prune_keys(pre_prepares_);
  prune_keys(prepares_);
  prune_keys(commits_);
  for (auto it = sent_commit_.begin(); it != sent_commit_.end();) {
    if (it->second <= stable_seq) it = sent_commit_.erase(it);
    else ++it;
  }
  for (auto it = checkpoints_.begin(); it != checkpoints_.end();) {
    if (it->first <= stable_seq) it = checkpoints_.erase(it);
    else ++it;
  }
  for (auto it = pending_execution_.begin(); it != pending_execution_.end();) {
    if (it->first <= stable_seq) it = pending_execution_.erase(it);
    else ++it;
  }
  for (auto it = snapshots_.begin(); it != snapshots_.end();) {
    if (it->first < stable_seq) it = snapshots_.erase(it);
    else ++it;
  }
  return out;
}

// -- view change (PBFT §4.4) --------------------------------------------
// Mirrors pbft_tpu/consensus/replica.py. Hot-path signatures are gated
// through the batched verifier; the evidence nested inside view-change
// messages (checkpoint certs, prepared certs, the VCs embedded in a
// NEW-VIEW) is verified inline on the host — view changes are rare
// reconfiguration events, not the throughput path.

bool Replica::has_unexecuted() const {
  if (!pending_execution_.empty()) return true;
  for (const auto& [key, pp] : pre_prepares_) {
    if (key.second > executed_upto_) return true;
  }
  return false;
}

bool Replica::verify_inline(int64_t rid, const Message& m,
                            const std::string& sig_hex) const {
  if (rid < 0 || rid >= config_.n()) return false;
  uint8_t sig[64], digest[32];
  if (!from_hex(sig_hex, sig, 64)) return false;
  message_signable(m, digest);
  return ed25519_verify(config_.replicas[rid].pubkey, digest, 32, sig);
}

Actions Replica::start_view_change(int64_t new_view) {
  int64_t floor = in_view_change_ ? pending_view_ : view_;
  int64_t v = new_view < 0 ? floor + 1 : new_view;
  if (v <= floor) return {};
  in_view_change_ = true;
  pending_view_ = v;
  if (wal_ != nullptr) wal_->note_view(view_, true, v);
  counters["view_changes_started"] += 1;
  if (view_hook) view_hook("view_change_sent", v);
  ViewChange vc;
  vc.new_view = v;
  vc.last_stable_seq = low_mark_;
  vc.checkpoint_proof = stable_proof_;
  vc.prepared_proofs = prepared_proofs();
  vc.replica = id_;
  vc = sign(vc);
  my_view_change_ = vc;
  Actions out;
  out.broadcasts.push_back({Message(vc)});
  out.merge(on_view_change(vc));  // log our own
  return out;
}

Actions Replica::retransmit_view_change() {
  // Verbatim re-broadcast (ISSUE 12): no counter moves, nothing is
  // re-signed; receivers treat it as the duplicate it is, and a
  // primary-elect that already sent NEW-VIEW answers with the cached
  // NEW-VIEW (see on_view_change) — lost-frame recovery in the SAME view.
  if (!in_view_change_ || !my_view_change_) return {};
  Actions out;
  out.broadcasts.push_back({Message(*my_view_change_)});
  return out;
}

JsonArray Replica::prepared_proofs() const {
  // P: per sequence prepared above the low watermark, the pre-prepare +
  // its 2f matching backup prepares (highest view wins per sequence).
  //
  // Only evidence with VALID signatures ships (ISSUE 14): in MAC mode
  // the hot path accepts frames by their lane without checking the
  // embedded signature, so a sig-corrupting Byzantine peer can place
  // garbage-signature prepares in honest logs — shipping one would make
  // validators reject this replica's whole VIEW-CHANGE. A slot that
  // cannot assemble a fully-valid certificate is not claimed (client
  // retransmission re-orders it in the new view). In signature mode
  // every logged message was already verified: the filter is a no-op.
  std::map<int64_t, std::pair<int64_t, Json>> best;  // seq -> (view, entry)
  for (const auto& [key, pp] : pre_prepares_) {
    auto [view, seq] = key;
    if (seq <= low_mark_ || !prepared(key)) continue;
    int64_t prim = config_.primary_of(view);
    if (!verify_inline(prim, Message(pp), pp.sig)) continue;
    JsonArray preps;
    auto slot = prepares_.find(key);
    if (slot != prepares_.end()) {
      for (const auto& [rid, p] : slot->second) {
        if (rid != prim && p.digest == pp.digest &&
            verify_inline(p.replica, Message(p), p.sig)) {
          preps.push_back(p.to_json());
        }
      }
    }
    if ((int64_t)preps.size() < 2 * config_.f()) continue;
    JsonObject entry;
    entry.emplace("pre_prepare", pp.to_json());
    entry.emplace("prepares", Json(std::move(preps)));
    auto it = best.find(seq);
    if (it == best.end() || view > it->second.first) {
      best[seq] = {view, Json(std::move(entry))};
    }
  }
  JsonArray out;
  for (auto& [seq, vp] : best) out.push_back(std::move(vp.second));
  return out;
}

namespace {
// THE quorum rule for stable-checkpoint evidence: the digest backed by
// >= quorum *distinct replicas* in a checkpoint proof, or nullptr. Used by
// both validate_view_change (to accept a proof) and stable_digest_for (to
// pick the digest adopted during the watermark jump) — a proof may also
// carry correctly-signed checkpoints with a minority (Byzantine) digest, so
// neither entry order nor repeated entries from one replica may influence
// the choice.
const std::string* majority_digest(const JsonArray& proof, int64_t quorum) {
  std::set<int64_t> seen;
  std::map<std::string, int64_t> by_digest;
  for (const Json& d : proof) {
    const Json* rid = d.find("replica");
    const Json* dig = d.find("digest");
    if (!rid || !dig || !dig->is_string()) continue;
    if (!seen.insert(rid->as_int()).second) continue;
    by_digest[dig->as_string()] += 1;
  }
  for (const Json& d : proof) {
    const Json* dig = d.find("digest");
    if (dig && dig->is_string() && by_digest[dig->as_string()] >= quorum)
      return &dig->as_string();
  }
  return nullptr;
}
}  // namespace

bool Replica::validate_view_change(const ViewChange& vc) const {
  // C: 2f+1 checkpoint messages proving last_stable_seq.
  if (vc.last_stable_seq > 0) {
    std::set<int64_t> seen;
    for (const Json& d : vc.checkpoint_proof) {
      auto m = message_from_json(d);
      if (!m) return false;
      auto* cp = std::get_if<Checkpoint>(&*m);
      if (!cp || cp->seq != vc.last_stable_seq) return false;
      if (seen.count(cp->replica)) return false;
      if (!verify_inline(cp->replica, *m, cp->sig)) return false;
      seen.insert(cp->replica);
    }
    if (!majority_digest(vc.checkpoint_proof, 2 * config_.f() + 1))
      return false;
  }
  // P: each prepared certificate internally consistent + signed.
  for (const Json& proof : vc.prepared_proofs) {
    const Json* ppd = proof.find("pre_prepare");
    const Json* preps = proof.find("prepares");
    if (!ppd || !preps || !preps->is_array()) return false;
    auto ppm = message_from_json(*ppd);
    if (!ppm) return false;
    auto* pp = std::get_if<PrePrepare>(&*ppm);
    if (!pp || pp->seq <= vc.last_stable_seq) return false;
    int64_t prim = config_.primary_of(pp->view);
    if (pp->replica != prim || pp->batch_digest() != pp->digest)
      return false;
    if (!verify_inline(prim, *ppm, pp->sig)) return false;
    std::set<int64_t> seen;
    for (const Json& pd : preps->as_array()) {
      auto pm = message_from_json(pd);
      if (!pm) return false;
      auto* p = std::get_if<Prepare>(&*pm);
      if (!p) return false;
      if (p->view != pp->view || p->seq != pp->seq || p->digest != pp->digest)
        return false;
      if (p->replica == prim || seen.count(p->replica)) return false;
      if (!verify_inline(p->replica, *pm, p->sig)) return false;
      seen.insert(p->replica);
    }
    if ((int64_t)seen.size() < 2 * config_.f()) return false;
  }
  return true;
}

Actions Replica::on_view_change(const ViewChange& vc) {
  if (vc.new_view <= view_) {
    // A VIEW-CHANGE for a view we already lead means the sender missed
    // our NEW-VIEW broadcast (lost frame, or its retransmission timer):
    // resend the cached message point-to-point — no recomputation, no
    // re-broadcast (ISSUE 12 NEW-VIEW retransmission/suppression).
    if (vc.new_view == view_ && config_.primary_of(vc.new_view) == id_ &&
        vc.replica != id_ && vc.replica >= 0 && vc.replica < config_.n()) {
      auto it = new_view_sent_.find(vc.new_view);
      if (it != new_view_sent_.end()) {
        Actions out;
        out.sends.push_back({vc.replica, Message(it->second)});
        return out;
      }
    }
    return {};
  }
  auto& slot = view_changes_[vc.new_view];
  if (slot.count(vc.replica)) return {};
  if (!validate_view_change(vc)) return {};
  slot.emplace(vc.replica, vc);
  Actions out;
  // Join rule (§4.5.2): f+1 replicas already moved past our view -> join
  // the smallest such view even if our own timer has not fired.
  int64_t floor = in_view_change_ ? pending_view_ : view_;
  std::set<int64_t> voters;
  int64_t smallest = -1;
  for (const auto& [v, reps] : view_changes_) {
    if (v > floor) {
      for (const auto& [rid, _] : reps) voters.insert(rid);
      if (smallest < 0) smallest = v;
    }
  }
  if ((int64_t)voters.size() >= config_.f() + 1) {
    out.merge(start_view_change(smallest));
  }
  if (config_.primary_of(vc.new_view) == id_) {
    out.merge(maybe_new_view(vc.new_view));
  }
  return out;
}

std::pair<int64_t, std::vector<Replica::OEntry>> Replica::compute_o(
    const std::vector<ViewChange>& vcs) const {
  int64_t min_s = 0;
  for (const auto& vc : vcs) min_s = std::max(min_s, vc.last_stable_seq);
  // seq -> (view, digest, request batch)
  std::map<int64_t, std::tuple<int64_t, std::string, std::vector<ClientRequest>>>
      best;
  auto parse_one = [](const Json& rj, std::vector<ClientRequest>* out) {
    if (rj.is_object() && rj.find("operation") && rj.find("timestamp") &&
        rj.find("client")) {
      ClientRequest parsed;
      parsed.operation = rj.find("operation")->as_string();
      parsed.timestamp = rj.find("timestamp")->as_int();
      parsed.client = rj.find("client")->as_string();
      out->push_back(std::move(parsed));
    }
  };
  for (const auto& vc : vcs) {
    for (const Json& proof : vc.prepared_proofs) {
      const Json* ppd = proof.find("pre_prepare");
      if (!ppd) continue;
      const Json* seqj = ppd->find("seq");
      const Json* viewj = ppd->find("view");
      const Json* digj = ppd->find("digest");
      if (!seqj || !viewj || !digj) continue;
      int64_t n = seqj->as_int();
      if (n <= min_s) continue;
      auto it = best.find(n);
      if (it == best.end() || viewj->as_int() > std::get<0>(it->second)) {
        // Legacy evidence carries the singular `request`; batched
        // evidence the `requests` list. The whole batch rides along.
        std::vector<ClientRequest> reqs;
        if (const Json* reqj = ppd->find("request")) {
          parse_one(*reqj, &reqs);
        } else if (const Json* reqsj = ppd->find("requests");
                   reqsj && reqsj->is_array()) {
          for (const Json& rj : reqsj->as_array()) parse_one(rj, &reqs);
        }
        best[n] = {viewj->as_int(), digj->as_string(), std::move(reqs)};
      }
    }
  }
  std::vector<OEntry> entries;
  int64_t max_s = best.empty() ? min_s : best.rbegin()->first;
  for (int64_t n = min_s + 1; n <= max_s; ++n) {
    auto it = best.find(n);
    if (it != best.end()) {
      entries.push_back(
          {n, std::get<1>(it->second), std::get<2>(it->second)});
    } else {
      // Gap filler: an EMPTY batch (the batched form of §4.4's null
      // request) — execution is a no-op, the sequence still advances.
      entries.push_back({n, batch_digest_hex({}), {}});
    }
  }
  return {min_s, entries};
}

namespace {
// The view-change whose checkpoint proof certifies min_s with a 2f+1
// majority, or nullptr. Callers adopt both the digest AND the proof: a
// replica whose watermark advances through a NEW-VIEW's min_s must also
// adopt the certificate, or its next VIEW-CHANGE claims last_stable_seq =
// min_s while attaching the stale pre-jump proof — which honest
// validators reject, wedging every future view change that needs this
// replica's vote (found by the chaos soak, mirrored in replica.py).
const ViewChange* stable_vc_for(const std::vector<ViewChange>& vcs,
                                int64_t min_s, int64_t f) {
  for (const auto& vc : vcs) {
    if (vc.last_stable_seq != min_s || vc.checkpoint_proof.empty()) continue;
    if (majority_digest(vc.checkpoint_proof, 2 * f + 1)) return &vc;
  }
  return nullptr;
}
}  // namespace

Actions Replica::maybe_new_view(int64_t v) {
  if (new_view_sent_.count(v)) return {};
  auto it = view_changes_.find(v);
  if (it == view_changes_.end() ||
      (int64_t)it->second.size() < 2 * config_.f() + 1)
    return {};
  // Deterministic V: the 2f+1 lowest replica ids (std::map iterates sorted).
  std::vector<ViewChange> vcs;
  for (const auto& [rid, vc] : it->second) {
    if ((int64_t)vcs.size() >= 2 * config_.f() + 1) break;
    vcs.push_back(vc);
  }
  auto [min_s, entries] = compute_o(vcs);
  std::vector<PrePrepare> pps;
  for (const auto& e : entries) {
    PrePrepare pp;
    pp.view = v;
    pp.seq = e.seq;
    pp.digest = e.digest;
    pp.requests = e.requests;
    pp.replica = id_;
    pps.push_back(sign(pp));
  }
  NewView nv;
  nv.new_view = v;
  for (const auto& vc : vcs) nv.view_changes.push_back(vc.to_json());
  for (const auto& pp : pps) nv.pre_prepares.push_back(pp.to_json());
  nv.replica = id_;
  nv = sign(nv);
  new_view_sent_.emplace(v, nv);
  Actions out;
  out.broadcasts.push_back({Message(nv)});
  out.merge(enter_new_view(v, min_s, stable_vc_for(vcs, min_s, config_.f()), pps));
  return out;
}

Actions Replica::on_new_view(const NewView& nv) {
  if (nv.new_view < view_ || (nv.new_view == view_ && !in_view_change_))
    return {};
  if (nv.replica != config_.primary_of(nv.new_view)) return {};
  std::vector<ViewChange> vcs;
  std::set<int64_t> seen;
  for (const Json& d : nv.view_changes) {
    auto m = message_from_json(d);
    if (!m) return {};
    auto* vc = std::get_if<ViewChange>(&*m);
    if (!vc || vc->new_view != nv.new_view) return {};
    if (seen.count(vc->replica)) return {};
    if (!verify_inline(vc->replica, *m, vc->sig)) return {};
    if (!validate_view_change(*vc)) return {};
    seen.insert(vc->replica);
    vcs.push_back(*vc);
  }
  if ((int64_t)vcs.size() < 2 * config_.f() + 1) return {};
  // O must equal our own recomputation from V (a Byzantine new primary
  // cannot smuggle in requests nobody prepared).
  auto [min_s, entries] = compute_o(vcs);
  if (nv.pre_prepares.size() != entries.size()) return {};
  std::vector<PrePrepare> pps;
  for (size_t i = 0; i < entries.size(); ++i) {
    auto m = message_from_json(nv.pre_prepares[i]);
    if (!m) return {};
    auto* pp = std::get_if<PrePrepare>(&*m);
    if (!pp) return {};
    if (pp->view != nv.new_view || pp->seq != entries[i].seq ||
        pp->digest != entries[i].digest || pp->replica != nv.replica)
      return {};
    if (pp->batch_digest() != pp->digest) return {};
    if (!verify_inline(pp->replica, *m, pp->sig)) return {};
    pps.push_back(*pp);
  }
  return enter_new_view(nv.new_view, min_s, stable_vc_for(vcs, min_s, config_.f()),
                        pps);
}

Actions Replica::enter_new_view(int64_t v, int64_t min_s,
                                const ViewChange* stable_vc,
                                const std::vector<PrePrepare>& pps) {
  // Tentative executions do not survive a view change (§5.3): roll the
  // uncommitted suffix back BEFORE processing the new view's O — its
  // re-issued pre-prepares re-run the three-phase protocol.
  rollback_tentative();
  view_ = v;
  in_view_change_ = false;
  pending_view_ = 0;
  if (wal_ != nullptr) wal_->note_view(v, false, 0);
  my_view_change_.reset();
  // Keep only the NEW-VIEW for the view we just entered (a laggard's
  // retransmitted VIEW-CHANGE may still ask for it); older entries can
  // never be requested again.
  for (auto it = new_view_sent_.begin(); it != new_view_sent_.end();) {
    if (it->first < v) it = new_view_sent_.erase(it);
    else ++it;
  }
  sealed_ts_.clear();  // per-view primary ordering memory
  counters["view_changes_completed"] += 1;
  if (view_hook) view_hook("new_view_installed", v);
  for (auto it = view_changes_.begin(); it != view_changes_.end();) {
    if (it->first <= v) it = view_changes_.erase(it);
    else ++it;
  }
  Actions out;
  const std::string* stable_digest =
      stable_vc ? majority_digest(stable_vc->checkpoint_proof,
                                  2 * config_.f() + 1)
                : nullptr;
  if (min_s > low_mark_ && stable_digest) {
    // Adopt the certificate with the watermark: our next VIEW-CHANGE's C
    // component must certify THIS stable seq, not the pre-jump one.
    JsonArray adopted;
    std::set<int64_t> seen;
    for (const Json& d : stable_vc->checkpoint_proof) {
      const Json* dig = d.find("digest");
      const Json* rid = d.find("replica");
      if (dig && dig->is_string() && dig->as_string() == *stable_digest &&
          rid && seen.insert(rid->as_int()).second) {
        adopted.push_back(d);
      }
    }
    stable_proof_ = std::move(adopted);
    out.merge(advance_watermark(min_s, *stable_digest));
  }
  // The new primary continues the sequence after the re-issued slots.
  // low_mark is included: when this replica's stable checkpoint is ahead of
  // min_s, seqs <= low_mark are executed everywhere and would never reply.
  seq_counter_ = std::max(min_s, low_mark_);
  for (const auto& pp : pps) seq_counter_ = std::max(seq_counter_, pp.seq);
  // Prune normal-case log entries from abandoned views above min_s that the
  // quorum did not re-issue: they can never prepare in view v, and keeping
  // them makes has_unexecuted() fire the request timer forever.
  std::set<int64_t> reissued;
  for (const auto& pp : pps) reissued.insert(pp.seq);
  auto prune_old_views = [&](auto& log) {
    for (auto it = log.begin(); it != log.end();) {
      if (it->first.first < v && !reissued.count(it->first.second))
        it = log.erase(it);
      else
        ++it;
    }
  };
  prune_old_views(pre_prepares_);
  prune_old_views(prepares_);
  prune_old_views(commits_);
  for (const auto& pp : pps) out.merge(on_pre_prepare(pp));
  // Re-aim forwarded-but-unexecuted client requests at the NEW primary
  // (ISSUE 12): a request forwarded to a primary that was just voted
  // out evaporated with the old view — without this the only recovery
  // is the client's retransmission timer, and until it fires the
  // request timers keep escalating further view changes with nothing to
  // order (the storm the chaos bench measures). Exactly-once is
  // untouched: duplicates die on the per-client timestamp guards.
  {
    std::vector<ClientRequest> reaim;
    for (auto it = forwarded_.begin(); it != forwarded_.end();) {
      auto last = last_timestamp_.find(it->first);
      if (last != last_timestamp_.end() &&
          it->second.timestamp <= last->second) {
        it = forwarded_.erase(it);  // already executed
        continue;
      }
      reaim.push_back(it->second);
      ++it;
    }
    const int64_t new_primary = config_.primary_of(v);
    for (const auto& req : reaim) {
      if (new_primary == id_) {
        out.merge(on_client_request(req));
      } else {
        out.sends.push_back({new_primary, Message(req)});
      }
    }
  }
  return out;
}

}  // namespace pbft
