#include "metrics.h"

#include <algorithm>
#include <cmath>
#include <cstdio>

namespace pbft {

namespace {

// Bucket edges mirror pbft_tpu/utils/trace_schema.py
// (LATENCY_BUCKETS_S / BATCH_SIZE_BUCKETS) — the lint compares values.
const std::vector<double> kLatencyBuckets = {
    0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025,
    0.05,   0.1,     0.25,   0.5,   1.0,    2.5,   5.0,  10.0};
const std::vector<double> kSizeBuckets = {1,   2,   4,   8,    16,   32,  64,
                                          128, 256, 512, 1024, 2048, 4096};

const char* kCounterNames[] = {
    "pbft_frames_in_total",          "pbft_executed_total",
    "pbft_view_changes_total",       "pbft_verify_batches_total",
    "pbft_verify_items_total",       "pbft_verify_rejected_total",
    "pbft_verify_deadline_fired_total",
    // Batches verified on the host although a verify service is
    // configured (service warming / unreachable / dead / past deadline).
    "pbft_verify_service_fallbacks_total",
    // Wire-codec surface: the serialize-once invariant counter (encodes
    // per broadcast, never per peer — tests compare it against the
    // broadcast count).
    "pbft_broadcast_encodes_total",
    // Batching surface (ISSUE 4): requests executed vs three-phase
    // instances executed — their ratio is the batch amplification.
    "pbft_requests_executed_total", "pbft_consensus_rounds_total",
    // Chaos surface (ISSUE 5): fault behaviors fired by --fault, frames
    // dropped by the seeded --chaos-drop-pct link knob.
    "pbft_faults_injected_total", "pbft_chaos_dropped_total",
    // Verify-service surface (ISSUE 7): launches shipped by the
    // coalescing dispatcher. Zero on a replica (eager registration keeps
    // the series set uniform across every runtime's scrape).
    "pbft_verify_service_launches_total",
    // Scale-out surface (ISSUE 10): poller wait() returns, bounded-queue
    // drops + partial-write episodes, requests received over gateway
    // links.
    "pbft_epoll_wakeups_total", "pbft_write_backpressure_events_total",
    "pbft_gateway_forwarded_total",
    // Perf-under-faults surface (ISSUE 12): explicit admission-control
    // rejections and gateway-fabric link replacements (a replica losing a
    // live gateway link).
    "pbft_overload_rejections_total", "pbft_gateway_failovers_total",
    // Multi-core surface (ISSUE 13): eventfd/pipe wakes crossing the
    // loop-shard / crypto-pipeline / consensus thread boundaries.
    "pbft_cross_thread_wakes_total",
    // The front-end threads (ISSUE 40): each shard thread's and each
    // pipeline thread's wall time by kind of work, microseconds, summed
    // over a replica's shards / pipelines (net_shard.h FrontClock), and
    // the messages lost at a thread boundary (0 in a healthy run).
    "pbft_shard_wait_us_total", "pbft_shard_read_us_total",
    "pbft_shard_send_us_total", "pbft_shard_other_us_total",
    "pbft_pipe_wait_us_total", "pbft_pipe_decode_us_total",
    "pbft_pipe_encode_us_total", "pbft_pipe_other_us_total",
    "pbft_shard_dropped_total",
    // Fast-path surface (ISSUE 14): MAC-vector authenticated frames
    // sent, sequences executed at PREPARED, tentative rollbacks.
    "pbft_mac_frames_total", "pbft_tentative_executions_total",
    "pbft_tentative_rollbacks_total",
    // What the fast path adds (ISSUE 32): seals the primary was refused by
    // a closed watermark window, and signatures checked on the host in
    // the normal case (a checkpoint's embedded one, in MAC mode).
    "pbft_seal_refused_total", "pbft_inline_verifies_total",
    // Verify batches launched while the verdicts of the batch before were
    // kept (ISSUE 37): that trip runs behind the replica's own pass.
    "pbft_verify_launched_ahead_total",
    // The loop thread's wall time by kind of work, microseconds (ISSUE
    // 38): the seven stages of net.h's LoopClock and their sum, folded
    // from plain integers where a scrape or /status is rendered.
    // pbft_epoll_wakeups_total is the count of passes they were spent in.
    "pbft_loop_us_total", "pbft_loop_wait_us_total",
    "pbft_loop_read_us_total", "pbft_loop_protocol_us_total",
    "pbft_loop_wal_us_total", "pbft_loop_send_us_total",
    "pbft_loop_verify_us_total", "pbft_loop_other_us_total",
    // One flush a connection an emit (ISSUE 41): frames handed to a
    // connection's send queue and send() calls made, the loop's and with
    // --net-threads the shards' added up; their ratio is how many frames
    // a system call carries.
    "pbft_frames_out_total", "pbft_send_calls_total",
    // Signatures this replica made (Replica::sign): every reply carries
    // one, so it is the largest countable item inside `protocol`.
    "pbft_signs_total",
    // Durable-recovery surface (ISSUE 15): WAL records appended, group-
    // commit fsync syscalls, and file bytes written.
    "pbft_wal_appends_total", "pbft_wal_fsyncs_total",
    "pbft_wal_bytes_total",
};
const char* kGaugeNames[] = {
    "pbft_verify_queue_depth",
    "pbft_verify_inflight_age_seconds",
    "pbft_verify_pool_threads",
    "pbft_verify_pool_queue_depth",
    "pbft_verify_pool_utilization",
    // Verify-service warmup cost (ISSUE 7): once-per-deploy compile
    // seconds, split cold (traced+compiled) vs warm (export/cache
    // reload). Zero on a replica.
    "pbft_verify_service_cold_compile_seconds",
    "pbft_verify_service_warm_compile_seconds",
    // Scale-out surface (ISSUE 10): live sockets (accepted + dialed),
    // refreshed by the end-of-iteration sweep.
    "pbft_connections_open",
    // View-timer backoff level (ISSUE 12, §4.5.2): 1 = fresh, doubles
    // per consecutive no-progress expiry — sustained high = no converge.
    "pbft_view_timer_backoff_level",
    // Multi-core surface (ISSUE 13): event-loop shard threads this
    // replica runs (1 = classic single loop) and the aggregate depth of
    // the crypto-pipeline offload queues.
    "pbft_net_loop_threads",
    "pbft_crypto_offload_queue_depth",
    // Durable-recovery surface (ISSUE 15): wall seconds the last WAL
    // replay + state reinstall took (0 = no recovery this life).
    "pbft_recovery_seconds",
    // Health-introspection surface (ISSUE 16): resident set, open fds,
    // WAL on-disk bytes, seconds since executed_upto last advanced, and
    // the verify-inbox depth — refreshed lazily at scrape/status time.
    "pbft_process_rss_bytes",
    "pbft_open_fds",
    "pbft_wal_disk_bytes",
    "pbft_last_progress_seconds",
    "pbft_inbox_depth",
};
// name -> uses the size bucket ladder (else latency).
const std::pair<const char*, bool> kHistogramNames[] = {
    {"pbft_verify_batch_size", true},
    {"pbft_verify_pool_window_size", true},
    {"pbft_batch_size", true},
    {"pbft_verify_service_window_size", true},
    {"pbft_verify_service_coalesced_clients", true},
    {"pbft_verify_seconds", false},
    {"pbft_phase_pre_prepare_seconds", false},
    {"pbft_phase_prepare_seconds", false},
    {"pbft_phase_commit_seconds", false},
    {"pbft_phase_reply_seconds", false},
    {"pbft_request_reply_seconds", false},
    // One verify trip, the replica's share: the oldest item's wait in the
    // verify inbox (once per batch, at launch) and one WAL group-commit
    // flush (write + fsync).
    {"pbft_verify_inbox_wait_seconds", false},
    {"pbft_wal_flush_seconds", false},
    // Verdicts read -> their delivery began (once a batch on the async
    // branch): what launching the next span first adds to a batch.
    {"pbft_verdict_held_seconds", false},
    // Their delivery began -> its last send left (once a kept batch):
    // dispatch, execute, sign, WAL flush, sends for one batch's verdicts.
    {"pbft_verdict_apply_seconds", false},
    // Pipeline -> consensus thread, once a drain that found something:
    // the drain's instant minus the push of the oldest entry it took.
    {"pbft_shard_handoff_seconds", false},
    // The oldest request's wait at the primary until its batch is sealed
    // (once a batch), and how long a tentative execution stayed revocable
    // (once a sequence number, tentative mode).
    {"pbft_request_wait_seconds", false},
    {"pbft_tentative_commit_lag_seconds", false},
};

// JSONL trace events net.cc emits (trace_batch, trace_view_change,
// trace_consensus_span, trace_verify_deadline, plus the ISSUE 9
// request-level waterfall and view-change span events).
const char* kTraceEventNames[] = {
    "verify_batch",
    "view_change_start",
    "consensus_span",
    "verify_deadline_fired",
    "request_rx",
    "batch_sealed",
    "reply_tx",
    "view_timer_fired",
    "view_change_sent",
    "new_view_installed",
    "commit_lag",
};

// Integer-valued samples print without a decimal point, matching the
// Python renderer's _fmt (so mixed-runtime scrapes diff cleanly).
std::string fmt_value(double v) {
  char buf[64];
  if (std::isfinite(v) && v == std::floor(v) && std::fabs(v) < 1e15) {
    std::snprintf(buf, sizeof(buf), "%lld", (long long)v);
  } else {
    std::snprintf(buf, sizeof(buf), "%g", v);
  }
  return buf;
}

}  // namespace

void MetricHistogram::observe(double v) {
  size_t i = std::lower_bound(edges.begin(), edges.end(), v) - edges.begin();
  counts[i] += 1;
  sum += v;
  count += 1;
}

Metrics::Metrics() {
  for (const char* n : kCounterNames) counters_[n] = 0;
  for (const char* n : kGaugeNames) gauges_[n] = 0;
  for (const auto& [n, size_buckets] : kHistogramNames) {
    MetricHistogram h;
    h.edges = size_buckets ? kSizeBuckets : kLatencyBuckets;
    h.counts.assign(h.edges.size() + 1, 0);
    histograms_[n] = std::move(h);
  }
}

void Metrics::inc(const char* name, int64_t n) {
  if (!enabled) return;
  auto it = counters_.find(name);
  if (it != counters_.end()) it->second += n;
}

void Metrics::set_gauge(const char* name, double v) {
  if (!enabled) return;
  auto it = gauges_.find(name);
  if (it != gauges_.end()) it->second = v;
}

void Metrics::observe(const char* name, double v) {
  if (!enabled) return;
  auto it = histograms_.find(name);
  if (it != histograms_.end()) it->second.observe(v);
}

std::string Metrics::render_prometheus(
    const std::string& replica_label) const {
  const std::string label = "{replica=\"" + replica_label + "\"}";
  const std::string label_open = "{replica=\"" + replica_label + "\",";
  std::string out;
  // One sorted pass over all names (maps are sorted; merge by name so the
  // ordering matches the Python renderer's single sorted dict).
  std::vector<std::string> names;
  for (const auto& [n, _] : counters_) names.push_back(n);
  for (const auto& [n, _] : gauges_) names.push_back(n);
  for (const auto& [n, _] : histograms_) names.push_back(n);
  std::sort(names.begin(), names.end());
  for (const auto& name : names) {
    if (auto c = counters_.find(name); c != counters_.end()) {
      out += "# TYPE " + name + " counter\n";
      out += name + label + " " + fmt_value((double)c->second) + "\n";
    } else if (auto g = gauges_.find(name); g != gauges_.end()) {
      out += "# TYPE " + name + " gauge\n";
      out += name + label + " " + fmt_value(g->second) + "\n";
    } else {
      const MetricHistogram& h = histograms_.at(name);
      out += "# TYPE " + name + " histogram\n";
      int64_t cum = 0;
      for (size_t i = 0; i < h.edges.size(); ++i) {
        cum += h.counts[i];
        out += name + "_bucket" + label_open + "le=\"" +
               fmt_value(h.edges[i]) + "\"} " + fmt_value((double)cum) + "\n";
      }
      cum += h.counts.back();
      out += name + "_bucket" + label_open + "le=\"+Inf\"} " +
             fmt_value((double)cum) + "\n";
      out += name + "_sum" + label + " " + fmt_value(h.sum) + "\n";
      out += name + "_count" + label + " " + fmt_value((double)h.count) + "\n";
    }
  }
  return out;
}

std::vector<std::string> Metrics::metric_names() {
  std::vector<std::string> names;
  for (const char* n : kCounterNames) names.push_back(n);
  for (const char* n : kGaugeNames) names.push_back(n);
  for (const auto& [n, _] : kHistogramNames) names.push_back(n);
  std::sort(names.begin(), names.end());
  return names;
}

std::vector<std::string> Metrics::trace_event_names() {
  std::vector<std::string> names;
  for (const char* n : kTraceEventNames) names.push_back(n);
  std::sort(names.begin(), names.end());
  return names;
}

}  // namespace pbft
