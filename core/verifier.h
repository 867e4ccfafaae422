// Pluggable signature-verifier backends (BASELINE.json north_star):
// `Verifier::verify_batch(items) -> bitmap`.
//
// - CpuVerifier: in-process Ed25519 batch verification through the
//   process-wide worker pool (core/verify_pool.cc): fixed RLC windows
//   (random-linear-combination check + Pippenger MSM, bisecting failing
//   windows to per-item verify) dispatched across threads — the control
//   arm (BASELINE.json configs 1-2). Pooled and serial verification share
//   window boundaries, so the accept set is thread-count independent; see
//   the accept-set note in ed25519.cc for the one documented divergence
//   from strict per-item semantics (colluding torsion-defect pairs inside
//   one window).
// - RemoteVerifier: ships (pubkey, digest, sig) batches over a local socket
//   to the colocated JAX/TPU service (pbft_tpu/net/service.py), which runs
//   one vmap'd XLA launch per batch and returns the validity bitmap.
//   Protocol: u32be count, then count * (32+32+64) bytes; reply = count
//   bytes of 0/1. Falls back to CPU when the service is unreachable so a
//   verifier outage degrades throughput, not safety/liveness.
//   Readiness handshake (ISSUE 7, pbft_tpu/net/verify_service.py): the
//   dial uses a SHORT connect deadline, then a count-0 status probe
//   returns 8 bytes ('V' 'S' version state u16be devices u16be warmed
//   shapes). state warming -> this verifier reports unusable and the
//   caller's fallback (the PR-2 native verify pool) carries the traffic,
//   re-probing at a gentle cadence until the service reports ready — a
//   cold accelerator can never block consensus. state ready / cpu-only
//   -> the service is used (a cpu-only service still coalesces windows
//   across every colocated daemon). A legacy service that never answers
//   the probe is assumed ready after the probe deadline — on a FRESH
//   probe-free connection: the timed-out stream is dropped, so a
//   slow-but-modern service answering the probe late can never mis-pair
//   its status bytes with a batch's verdict bytes.
#pragma once

#include <chrono>
#include <cstdint>
#include <string>
#include <vector>

namespace pbft {

struct VerifyItem {
  uint8_t pub[32];
  uint8_t msg[32];
  uint8_t sig[64];
};

class Verifier {
 public:
  virtual ~Verifier() = default;
  virtual std::vector<uint8_t> verify_batch(
      const std::vector<VerifyItem>& items) = 0;

  // Asynchronous protocol, for backends whose launch crosses a socket
  // (RemoteVerifier): the event loop must NOT stall for the round-trip —
  // it keeps draining peers while the launch runs, which is where the
  // batching window's occupancy comes from. Sync-only backends return
  // -1 from async_fd() and the caller uses verify_batch.
  virtual int async_fd() const { return -1; }
  // Send one batch without waiting for the verdicts. False = transport
  // unavailable (caller should verify this batch synchronously instead).
  virtual bool begin_batch(const std::vector<VerifyItem>& items) {
    (void)items;
    return false;
  }
  // Drain whatever verdict bytes are readable (call when poll() reports
  // async_fd readable). Returns true once the batch completed with *out
  // filled; on transport failure returns true with *failed set (the
  // caller re-verifies that batch via its fallback).
  virtual bool poll_result(std::vector<uint8_t>* out, bool* failed) {
    (void)out;
    *failed = true;
    return true;
  }
  // Abandon an inflight async batch (the caller hit its wedge deadline,
  // net.cc check_verify_deadline): drop the transport so a late reply
  // lands on a closed socket instead of mis-pairing with the next batch.
  virtual void cancel_inflight() {}
  // Batches this backend verified on the host because its service was
  // warming, unreachable or died mid-stream (RemoteVerifier's liveness
  // fallback). Reported so a cluster that never reached the device is
  // distinguishable from one that did. 0 for in-process backends.
  virtual int64_t host_fallbacks() const { return 0; }
  // How many verification lanes one dispatch can occupy — the event loop
  // sizes its accumulation window to capacity instead of one inflight
  // window (net.cc run_verify_batch). 1 for serial/remote backends; the
  // pool-backed CpuVerifier reports its thread count.
  virtual size_t parallel_capacity() const { return 1; }
};

class CpuVerifier : public Verifier {
 public:
  std::vector<uint8_t> verify_batch(
      const std::vector<VerifyItem>& items) override;
  size_t parallel_capacity() const override;
};

class RemoteVerifier : public Verifier {
 public:
  // target: "host:port" TCP or a unix socket path ("/...").
  explicit RemoteVerifier(std::string target);
  ~RemoteVerifier() override;
  std::vector<uint8_t> verify_batch(
      const std::vector<VerifyItem>& items) override;

  int async_fd() const override { return inflight_ ? fd_ : -1; }
  bool begin_batch(const std::vector<VerifyItem>& items) override;
  bool poll_result(std::vector<uint8_t>* out, bool* failed) override;
  void cancel_inflight() override;
  int64_t host_fallbacks() const override { return host_fallbacks_; }
  // Test hook: adopt an already-connected fd (e.g. a socketpair end).
  void adopt_fd_for_test(int fd) { fd_ = fd; }

  // Last observed readiness-handshake result (kUnknown before any
  // successful dial). Matches pbft_tpu/net/service.py STATE_* values.
  enum class ServiceState { kUnknown, kWarming, kReady, kCpuOnly };
  ServiceState service_state() const { return state_; }
  int service_devices() const { return devices_; }
  // Test hook: run the status probe/parse on an adopted fd.
  bool probe_status_for_test(bool allow_legacy = false) {
    return probe_status(allow_legacy);
  }

 private:
  bool ensure_connected();
  // Non-blocking connect bounded by connect_timeout_ms_ (a downed or
  // blackholed service must cost milliseconds, not an OS connect
  // timeout, on the consensus event loop's verify path).
  bool connect_with_deadline();
  // allow_legacy: a probe timeout right after connect means a
  // pre-handshake service — the target is remembered as legacy but the
  // call still returns false, because the timed-out probe is OUTSTANDING
  // on the stream: a slow-but-modern service answering late would
  // mis-pair 8 status bytes with the next batch's verdict bytes
  // (race_stress.cc's late-probe service mode reproduces this; pinned by
  // core_test test_remote_verifier_readiness). ensure_connected re-dials
  // legacy targets on a clean stream and uses them probe-free. On a
  // warming reprobe a timeout means a wedged service (drop, retry later).
  bool probe_status(bool allow_legacy);
  // Size async_budget_items_ from the connection's actual SO_SNDBUF
  // (called after every successful connect, including legacy re-dials).
  void tune_send_budget();
  void drop_connection();
  // The host fallback, counted (every path to the native pool goes here).
  std::vector<uint8_t> verify_on_host(const std::vector<VerifyItem>& items);
  std::string target_;
  int fd_ = -1;
  CpuVerifier fallback_;
  int64_t host_fallbacks_ = 0;
  ServiceState state_ = ServiceState::kUnknown;
  // Target answered no status probe once (pre-handshake service):
  // assumed ready, and reconnects skip the probe deadline entirely so a
  // deadline-dropped link never re-stalls the consensus event loop.
  bool legacy_ = false;
  int devices_ = 0;
  int warmed_ = 0;
  int connect_timeout_ms_ = 250;   // PBFT_VERIFY_CONNECT_MS
  int probe_timeout_ms_ = 1000;    // PBFT_VERIFY_PROBE_MS
  int reprobe_ms_ = 1000;          // warming/down re-check cadence
  // Backoff stamp: no connect/probe attempts before this instant, so a
  // dead or warming service costs at most one short probe per second
  // instead of one per verify window.
  std::chrono::steady_clock::time_point retry_after_{};
  // One batch in flight at a time (the service pairs one reply per
  // request on the connection, in order).
  bool inflight_ = false;
  std::vector<uint8_t> resp_;  // verdict bytes received so far
  size_t expect_ = 0;
  // Largest batch begin_batch will ship: derived from the connection's
  // actual SO_SNDBUF so the blocking request write always fits the
  // kernel buffer (default = safe under Linux's stock ~208 KiB wmem).
  size_t async_budget_items_ = 1500;
};

}  // namespace pbft
