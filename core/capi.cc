// C ABI exports for ctypes (Python <-> C++ equivalence tests and the
// Python-side use of the native CPU verifier). pybind11 is not available in
// this environment; ctypes over a plain C ABI is the binding layer.
#include <cstddef>
#include <cstdint>
#include <cstring>
#include <string>

#include "blake2b.h"
#include "ed25519.h"
#include "flight.h"
#include "messages.h"
#include "metrics.h"
#include "secure.h"
#include "sha512.h"
#include "verify_pool.h"

namespace {
// Shared copy-out for the newline-joined name tables below.
size_t join_names(const std::vector<std::string>& names, char* out,
                  size_t cap) {
  std::string joined;
  for (const auto& n : names) {
    if (!joined.empty()) joined.push_back('\n');
    joined += n;
  }
  if (joined.size() < cap) {
    std::memcpy(out, joined.data(), joined.size());
    out[joined.size()] = '\0';
  }
  return joined.size();
}
}  // namespace

extern "C" {

// Parse a JSON message payload, re-serialize canonically, and compute its
// signable digest. Returns the canonical length (0 on parse failure).
// Canonical bytes go to out_canonical (cap bytes), digest to out_digest[32].
// Used by the Python tests to prove C++ and Python encodings are
// byte-identical (SURVEY.md §7 "determinism at the FFI boundary").
size_t pbft_message_roundtrip(const uint8_t* payload, size_t payload_len,
                              uint8_t* out_canonical, size_t cap,
                              uint8_t out_digest[32]) {
  std::string text((const char*)payload, payload_len);
  auto msg = pbft::from_payload(text);
  if (!msg) return 0;
  std::string canon = pbft::message_canonical(*msg);
  if (canon.size() <= cap) {
    std::memcpy(out_canonical, canon.data(), canon.size());
  }
  pbft::message_signable(*msg, out_digest);
  return canon.size();
}

void pbft_blake2b(uint8_t* out, size_t outlen, const uint8_t* in,
                  size_t inlen) {
  pbft::blake2b(out, outlen, in, inlen);
}

void pbft_sha512(uint8_t out[64], const uint8_t* in, size_t inlen) {
  pbft::sha512(out, in, inlen);
}

void pbft_ed25519_public_key(uint8_t pub[32], const uint8_t seed[32]) {
  pbft::ed25519_public_key(pub, seed);
}

void pbft_ed25519_sign(uint8_t sig[64], const uint8_t seed[32],
                       const uint8_t* msg, size_t msglen) {
  pbft::ed25519_sign(sig, seed, msg, msglen);
}

int pbft_ed25519_verify(const uint8_t pub[32], const uint8_t* msg,
                        size_t msglen, const uint8_t sig[64]) {
  return pbft::ed25519_verify(pub, msg, msglen, sig) ? 1 : 0;
}

// Batch CPU verification (the control arm): items laid out as
// pubs[32*i], msgs[32*i], sigs[64*i]; out[i] = 1 if valid. Dispatched
// through the process-wide verify pool (core/verify_pool.cc): fixed RLC
// windows across worker threads, per-item bisect fallback per window —
// the same accept set as the serial path at every thread count.
void pbft_ed25519_verify_batch(const uint8_t* pubs, const uint8_t* msgs,
                               const uint8_t* sigs, uint8_t* out, size_t n) {
  pbft::global_verify_pool().verify(pubs, msgs, sigs, n, out);
}

// --- Verify-pool control surface (pbft_tpu/native.py, bench.py).

// Reconfigure the process-wide pool width (0 = hardware_concurrency).
// Tears down the existing pool; call only between batches.
void pbft_set_verify_threads(int threads) {
  pbft::set_global_verify_threads(threads);
}

// The pool's actual width (creates the pool at the configured width).
int pbft_verify_threads(void) {
  return pbft::global_verify_pool().threads();
}

// Lifetime pool counters as one JSON object (threads, batches, windows,
// items, busy/wall seconds, utilization, last queue depth/window items).
size_t pbft_verify_pool_stats_json(char* out, size_t cap) {
  pbft::VerifyPoolStats s = pbft::global_verify_pool().stats();
  char buf[512];
  int n = std::snprintf(
      buf, sizeof(buf),
      "{\"threads\":%d,\"batches\":%lld,\"windows\":%lld,\"items\":%lld,"
      "\"busy_seconds\":%.6f,\"wall_seconds\":%.6f,\"utilization\":%.6f,"
      "\"last_queue_depth\":%lld,\"last_window_items\":%lld}",
      s.threads, (long long)s.batches, (long long)s.windows,
      (long long)s.items, s.busy_seconds, s.wall_seconds, s.utilization(),
      (long long)s.last_queue_depth, (long long)s.last_window_items);
  if (n > 0 && (size_t)n < cap) {
    std::memcpy(out, buf, (size_t)n + 1);
  }
  return (size_t)n;
}

// Test hook (ADVICE round-5 medium): force the entropy-exhaustion path so
// the RLC fast path disables and windows verify per-item.
void pbft_test_force_entropy_exhaustion(int on) {
  pbft::ed25519_test_force_entropy_exhaustion(on != 0);
}

// Test hooks: the scalar arithmetic mod L (core/ed25519.h), held to
// Python's `int % L` by tests/test_native_crypto.py.
void pbft_test_sc_reduce512(uint8_t out[32], const uint8_t in[64]) {
  pbft::ed25519_test_sc_reduce512(out, in);
}

void pbft_test_sc_muladd(uint8_t out[32], const uint8_t a[32],
                         const uint8_t b[32], const uint8_t c[32]) {
  pbft::ed25519_test_sc_muladd(out, a, b, c);
}

void pbft_test_sc_muladd128(uint8_t out[32], const uint8_t a[16],
                            const uint8_t b[32], const uint8_t c[32]) {
  pbft::ed25519_test_sc_muladd128(out, a, b, c);
}

void pbft_test_sc_add(uint8_t out[32], const uint8_t a[32],
                      const uint8_t b[32]) {
  pbft::ed25519_test_sc_add(out, a, b);
}

// Per-key decompressed-point cache controls (window-prep memoization):
// clear drops entries; disable forces the cold path. The Python parity
// test pins warm/cold verdict equality through these.
void pbft_pubkey_cache_clear(void) { pbft::ed25519_pubkey_cache_clear(); }

void pbft_test_pubkey_cache_disable(int on) {
  pbft::ed25519_test_pubkey_cache_disable(on != 0);
}

// --- Binary-v2 wire codec surface (tests/test_wire_codec.py).
//
// Encode a message given as a JSON payload into the binary-v2 layout
// (returns the binary length, 0 when the type has no binary form or the
// payload doesn't parse; out must hold cap bytes). The Python side
// compares these bytes against its own to_binary output — the
// cross-runtime byte-parity fuzz.
size_t pbft_message_to_binary(const uint8_t* payload, size_t payload_len,
                              uint8_t* out, size_t cap) {
  std::string text((const char*)payload, payload_len);
  auto msg = pbft::from_payload(text);
  if (!msg) return 0;
  std::string bin;
  if (!pbft::message_to_binary(*msg, &bin)) return 0;
  if (bin.size() <= cap) std::memcpy(out, bin.data(), bin.size());
  return bin.size();
}

// Decode a binary-v2 payload and re-serialize canonically; also emits the
// signable digest derived from the payload (the receive-side reuse path).
// Returns the canonical length (0 on decode failure).
size_t pbft_message_from_binary(const uint8_t* payload, size_t payload_len,
                                uint8_t* out_canonical, size_t cap,
                                uint8_t out_digest[32]) {
  std::string text((const char*)payload, payload_len);
  auto msg = pbft::message_from_binary(text);
  if (!msg) return 0;
  std::string canon = pbft::message_canonical(*msg);
  if (canon.size() <= cap) std::memcpy(out_canonical, canon.data(), canon.size());
  pbft::message_signable_from_payload(text, *msg, out_digest);
  return canon.size();
}

// MAC-vector frame encode (ISSUE 14; tests/test_wire_codec.py fuzz):
// the message arrives as a JSON payload, the lanes as n x (rid:u8 ||
// tag:16B). Returns the frame length (0 when the type has no MAC form).
size_t pbft_message_to_binary_mac(const uint8_t* payload, size_t payload_len,
                                  const uint8_t* lanes, size_t n_lanes,
                                  uint8_t* out, size_t cap) {
  std::string text((const char*)payload, payload_len);
  auto msg = pbft::from_payload(text);
  if (!msg) return 0;
  std::vector<pbft::MacLane> vec;
  for (size_t i = 0; i < n_lanes; ++i) {
    pbft::MacLane lane;
    lane.rid = lanes[17 * i];
    std::memcpy(lane.tag, lanes + 17 * i + 1, 16);
    vec.push_back(lane);
  }
  std::string bin;
  if (!pbft::message_to_binary_mac(*msg, vec, &bin)) return 0;
  if (bin.size() <= cap) std::memcpy(out, bin.data(), bin.size());
  return bin.size();
}

// Lane extraction parity: 1 when the payload is a MAC frame carrying a
// lane for rid (tag copied out), 0 otherwise.
int pbft_mac_frame_lane(const uint8_t* payload, size_t payload_len,
                        long long rid, uint8_t out_tag[16]) {
  std::string text((const char*)payload, payload_len);
  return pbft::mac_frame_lane(text, (int64_t)rid, out_tag) ? 1 : 0;
}

// Authenticator tag parity (net/secure.py mac_tag).
void pbft_mac_tag(const uint8_t key[32], const uint8_t signable[32],
                  uint8_t out_tag[16]) {
  pbft::mac_tag(key, signable, out_tag);
}

// Lane key derivation parity (net/secure.py derive_auth_keys).
void pbft_derive_auth_keys(const uint8_t shared[32], const uint8_t eph_i[32],
                           const uint8_t eph_r[32], uint8_t out_i2r[32],
                           uint8_t out_r2i[32]) {
  pbft::derive_auth_keys(out_i2r, out_r2i, shared, eph_i, eph_r);
}

// Signable digest derived from a framed payload (JSON sig-splice or
// binary template) — the Python parity test compares this against the
// parse -> re-serialize derivation for every message type. Returns 1 on
// parse success.
int pbft_signable_from_payload(const uint8_t* payload, size_t payload_len,
                               uint8_t out_digest[32]) {
  std::string text((const char*)payload, payload_len);
  auto msg = pbft::from_payload(text);
  if (!msg) return 0;
  pbft::message_signable_from_payload(text, *msg, out_digest);
  return 1;
}

// --- Observability schema-parity surface (core/metrics.cc tables).
//
// The mixed-runtime contract (pbft_tpu/utils/trace_schema.py) requires
// both runtimes to emit identical metric and trace-event names; these
// exports let the Python parity test read the names the NATIVE runtime
// actually compiled in (scripts/check_trace_schema.py lints the sources
// statically; this is the runtime check). Newline-joined into out
// (NUL-terminated when it fits); returns the joined length.

size_t pbft_metric_names(char* out, size_t cap) {
  return join_names(pbft::Metrics::metric_names(), out, cap);
}

size_t pbft_trace_event_names(char* out, size_t cap) {
  return join_names(pbft::Metrics::trace_event_names(), out, cap);
}

// Render an empty (zero-valued) metrics registry as Prometheus text —
// the exposition-format parity check against the Python renderer.
size_t pbft_metrics_render_empty(const char* replica_label, char* out,
                                 size_t cap) {
  pbft::Metrics m;
  m.enabled = true;
  std::string text = m.render_prometheus(replica_label);
  if (text.size() < cap) {
    std::memcpy(out, text.data(), text.size());
    out[text.size()] = '\0';
  }
  return text.size();
}

// --- Black-box flight recorder (core/flight.{h,cc}; Python mirror
// pbft_tpu/utils/flight.py, decoder scripts/flight_dump.py). These
// exports let the tier-1 overhead-guard test drive the NATIVE ring:
// disabled record is a no-op, dump/decode round-trips through the shared
// binary format, and the Python decoder reads C++ dumps byte-for-byte.

// (Re)size + enable the process-wide ring; capacity 0 disables.
void pbft_flight_configure(size_t capacity) {
  pbft::global_flight().configure(capacity);
}

void pbft_flight_record(int ev, long long view, long long seq, int peer) {
  pbft::global_flight().record((uint16_t)ev, view, seq, peer);
}

// Total records ever accepted (not clamped to capacity).
unsigned long long pbft_flight_total(void) {
  return pbft::global_flight().total_recorded();
}

// Write the binary dump; returns the record count, -1 on failure.
long pbft_flight_dump(const char* path) {
  return pbft::global_flight().dump(path);
}

void pbft_flight_reset(void) { pbft::global_flight().reset(); }

// --- Secure-link primitives (interop pinning vs pbft_tpu/net/secure.py).

void pbft_blake2b_keyed(uint8_t* out, size_t outlen, const uint8_t* key,
                        size_t keylen, const uint8_t* in, size_t inlen) {
  pbft::blake2b_keyed(out, outlen, key, keylen, in, inlen);
}

void pbft_dh_public(uint8_t pub[32], const uint8_t secret[32]) {
  pbft::ed25519_dh_public(pub, secret);
}

int pbft_dh_shared(uint8_t out[32], const uint8_t secret[32],
                   const uint8_t peer_pub[32]) {
  return pbft::ed25519_dh_shared(out, secret, peer_pub) ? 1 : 0;
}

// sealed (= ct || 16B tag) written to out (cap in+16 bytes required).
void pbft_aead_seal(const uint8_t key[64], uint64_t ctr, const uint8_t* in,
                    size_t inlen, uint8_t* out) {
  std::string sealed =
      pbft::aead_seal(key, ctr, std::string((const char*)in, inlen));
  std::memcpy(out, sealed.data(), sealed.size());
}

// Returns plaintext length, or -1 on tag mismatch (out cap = inlen).
long pbft_aead_open(const uint8_t key[64], uint64_t ctr, const uint8_t* in,
                    size_t inlen, uint8_t* out) {
  auto pt = pbft::aead_open(key, ctr, std::string((const char*)in, inlen));
  if (!pt) return -1;
  std::memcpy(out, pt->data(), pt->size());
  return (long)pt->size();
}

}  // extern "C"
