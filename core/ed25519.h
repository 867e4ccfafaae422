// Ed25519 (RFC 8032) for the C++ replica core: the *CPU verifier backend*
// (the control arm of the CPU-vs-TPU A/B, BASELINE.json config 2) and the
// host-side signer used by pbftd.
//
// Our own implementation: GF(2^255-19) in 5x51-bit limbs with unsigned
// __int128 accumulation, complete twisted-Edwards addition (a=-1), Shamir
// double-scalar verification — the same verification equation and accept set
// as pbft_tpu.crypto.ref / pbft_tpu.crypto.ed25519 (cofactorless, strict
// S < L, canonical-A rejection). Equivalence-tested against both via ctypes.
//
// The reference generated an Ed25519 keypair but never signed or verified
// (reference src/main.rs:39, TODOs at src/behavior.rs:127,:185).
#pragma once

#include <cstddef>
#include <cstdint>

namespace pbft {

// Public key (32B) from a 32-byte seed.
void ed25519_public_key(uint8_t pub[32], const uint8_t seed[32]);

// Detached signature (64B = R||S) over msg.
void ed25519_sign(uint8_t sig[64], const uint8_t seed[32], const uint8_t* msg,
                  size_t msglen);

// Cofactorless RFC 8032 verify; strict S < L; rejects non-canonical A.
bool ed25519_verify(const uint8_t pub[32], const uint8_t* msg, size_t msglen,
                    const uint8_t sig[64]);

// Batch verification over 32-byte messages (the consensus digest shape):
// random-linear-combination check + Pippenger multi-scalar multiplication,
// bisecting failing windows down to per-item ed25519_verify (which stays
// the authority for every rejection). ~2-4x the per-item throughput on
// honest windows; see the accept-set note in ed25519.cc. Inputs are
// packed arrays (pubs: n*32, msgs: n*32, sigs: n*64); out: n bytes 0/1.
//
// The batch is processed in FIXED windows of kEd25519RlcWindowItems: one
// RLC check (+ bisect on failure) per window. Window boundaries depend
// only on item order — never on thread count — so the accept set of the
// serial path and core/verify_pool.cc's parallel path are identical by
// construction.
void ed25519_verify_batch(const uint8_t* pubs, const uint8_t* msgs,
                          const uint8_t* sigs, size_t n, uint8_t* out);

// One RLC window (n <= kEd25519RlcWindowItems enforced by callers; larger
// n still verifies correctly as a single oversized window). This is the
// unit of work core/verify_pool.cc hands to its workers; verify_batch is
// exactly a loop of these. Thread-safe: per-call state only (the comb
// table is built once under the magic-static lock).
void ed25519_verify_window(const uint8_t* pubs, const uint8_t* msgs,
                           const uint8_t* sigs, size_t n, uint8_t* out);

// The fixed RLC window width shared by the serial and pooled paths.
constexpr size_t kEd25519RlcWindowItems = 256;

// Test hook (ADVICE round-5 medium): simulate entropy exhaustion so the
// RLC fast path is disabled and windows verify per-item. Never set in
// production.
void ed25519_test_force_entropy_exhaustion(bool on);

// Test hooks: the scalar arithmetic mod L behind sign, verify and the batch
// path, on little-endian bytes, for the differential tests against
// Python's `int % L` (tests/test_native_crypto.py) and against bit-serial
// long division (core_test.cc). reduce512: a 64-byte value mod L.
// muladd: (a*b + c) mod L, any 256-bit operands. muladd128: the same with a
// 16-byte a (the batch coefficients). add: (a + b) mod L, a and b < L.
void ed25519_test_sc_reduce512(uint8_t out[32], const uint8_t in[64]);
void ed25519_test_sc_muladd(uint8_t out[32], const uint8_t a[32],
                            const uint8_t b[32], const uint8_t c[32]);
void ed25519_test_sc_muladd128(uint8_t out[32], const uint8_t a[16],
                               const uint8_t b[32], const uint8_t c[32]);
void ed25519_test_sc_add(uint8_t out[32], const uint8_t a[32],
                         const uint8_t b[32]);

// Per-key decompressed-point cache controls (window-prep memoization of
// pubkey decompression; see ed25519.cc). Clear drops all entries; the
// disable hook forces the cold path — tests/test_verify_pool.py pins
// warm/cold verdict parity through both.
void ed25519_pubkey_cache_clear();
void ed25519_test_pubkey_cache_disable(bool on);

// Ephemeral DH on edwards25519 for the secure-link handshake
// (core/secure.cc; mirror of pbft_tpu/net/secure.py dh_keypair/dh_shared).
// Public key from a 32-byte secret (clamped X25519-style).
void ed25519_dh_public(uint8_t pub[32], const uint8_t secret[32]);
// Shared secret = compress(clamp(secret) * peer point); false on an
// invalid peer encoding or a small-order (identity) result.
bool ed25519_dh_shared(uint8_t out[32], const uint8_t secret[32],
                       const uint8_t peer_pub[32]);

}  // namespace pbft
