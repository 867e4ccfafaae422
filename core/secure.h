// Authenticated, encrypted replica-replica links for pbftd — the C++ mirror
// of pbft_tpu/net/secure.py (one spec, two byte-compatible implementations;
// the module docstring there is the protocol definition). The reference
// secures every libp2p link with development_transport (Noise + yamux,
// reference src/main.rs:42) and names its protocol /ackintosh/pbft/1.0.0
// (reference src/protocol_config.rs:24); this is the rebuild's equivalent:
// signed ephemeral DH on edwards25519 + keyed-BLAKE2b encrypt-then-MAC,
// with the protocol version carried in the plaintext hello and rejected
// cleanly on mismatch.
#pragma once

#include <cstdint>
#include <optional>
#include <string>

#include "json.h"
#include "replica.h"  // ClusterConfig (identity pubkey table)

namespace pbft {

// 1.1.0 adds the negotiated binary-v2 payload codec (core/messages.h);
// 1.0.0 peers stay interoperable — the hello's ver gates what a sender
// may offer, and the transcript binds to the initiator's advertised
// version so mixed-version secure handshakes still agree on the bytes.
// 1.2.0 adds the batched pre-prepare (binary 0x06 / JSON `requests`,
// ISSUE 4); batch=1 frames stay byte-identical to 1.1.0, so 1.1.0 and
// 1.0.0 peers remain in the compatible set — a batching primary simply
// must not be pointed at them with batch_max_items > 1. 1.3.0 adds the
// fast-path modes (ISSUE 14): per-link session-MAC authenticators on
// normal-case frames (the MAC-vector binary variants, core/messages.h
// 0x12-0x16) and the tentative client-reply flag; a link runs MAC mode
// only when BOTH hellos offered kAuthModeMac, so every older peer falls
// back to signature mode byte-for-byte.
inline constexpr const char* kProtocolVersion = "pbft-tpu/1.3.0";
inline constexpr const char* kProtocolVersionBatch = "pbft-tpu/1.2.0";
inline constexpr const char* kProtocolVersionBin2 = "pbft-tpu/1.1.0";
inline constexpr const char* kProtocolVersionLegacy = "pbft-tpu/1.0.0";
inline constexpr size_t kTagLen = 16;

// Authenticator-mode offer in the 1.3.0 hello's "auth" list, the lane
// tag width, and the MAC domain-separation label (mirrored by
// pbft_tpu/net/secure.py AUTH_MODE_MAC / MAC_TAG_LEN / MAC_CONTEXT;
// constants lint).
inline constexpr const char* kAuthModeMac = "mac1";
inline constexpr size_t kMacTagLen = 16;
inline constexpr const char* kMacContext = "pbft-tpu-auth1|";

// The hello this node sends: kProtocolVersion with codecs ["bin2"] (and
// auth ["mac1"] when the fast path asked for it), the 1.2.0 hello under
// PBFT_PROTO_CAP=1.2.0, or the legacy 1.0.0 JSON-only hello when
// PBFT_WIRE_CODEC=json (the mixed-cluster escape hatches and the
// interop-test levers).
const char* wire_hello_version();
bool wire_offer_binary();
// Whether this node's hellos offer MAC mode: the config asked for it
// AND nothing capped the advertised protocol below 1.3.0.
bool wire_offer_mac(bool fastpath_mac);
// True when a peer's hello offers the binary-v2 codec (and this node
// offers it too): the sender may then encode hot messages as binary.
bool hello_offers_binary(const Json& obj);
// True when a peer's hello offers the MAC authenticator mode; callers
// AND it with their own offer.
bool hello_offers_mac(const Json& obj);

// One authenticator lane: keyed BLAKE2b(kMacContext || signable digest)
// under a 32-byte per-link session key. Byte-identical to
// net/secure.py mac_tag.
void mac_tag(const uint8_t key[32], const uint8_t signable[32],
             uint8_t out[kMacTagLen]);
// Constant-time lane comparison.
bool mac_tag_equal(const uint8_t a[kMacTagLen], const uint8_t b[kMacTagLen]);
// The two directions' authenticator session keys of one link, from the
// handshake's shared secret and ephemeral keys: keyed BLAKE2b-256 under
// the labels "a-i2r" / "a-r2i" (net/secure.py derive_auth_keys).
void derive_auth_keys(uint8_t a_i2r[32], uint8_t a_r2i[32],
                      const uint8_t shared[32], const uint8_t eph_i[32],
                      const uint8_t eph_r[32]);

// Keystream/tag primitive: sealed = ciphertext || 16B tag. key is 64 bytes
// (enc 32 || mac 32); ctr is the per-direction frame counter.
std::string aead_seal(const uint8_t key[64], uint64_t ctr,
                      const std::string& plaintext);
// Empty optional on tag mismatch (constant-time compare).
std::optional<std::string> aead_open(const uint8_t key[64], uint64_t ctr,
                                     const std::string& sealed);

// One connection's handshake state machine + sealed-frame codec.
//
// Thread ownership (ISSUE 13): a SecureChannel has exactly ONE owning
// thread at a time and no internal locking. In the single-loop runtime
// that is the event-loop thread for the channel's whole life. In the
// multi-core runtime the owning LOOP SHARD runs the handshake, then
// MOVES the established channel to its crypto pipeline thread (through
// the shard->pipeline command queue, which is the synchronization
// point); from then on every seal_frame/open_frame runs on that one
// pipeline thread, in command-FIFO order — which is exactly what keeps
// the per-direction frame counters (the AEAD nonce sequence) in step
// with the bytes on the wire.
class SecureChannel {
 public:
  // expected_peer = the dialed replica id (initiator side), or -1 to learn
  // the peer id from its authenticated handshake frame (responder side).
  // offer_mac: this node's hellos offer the MAC authenticator mode.
  // auth_only: run the SAME signed handshake purely for key agreement +
  // identity (the fastpath=mac, secure=false flavor) — frames on the
  // link stay plaintext and callers must not seal/open through it.
  SecureChannel(const ClusterConfig* cfg, int64_t my_id,
                const uint8_t identity_seed[32], bool initiator,
                int64_t expected_peer = -1, bool offer_mac = false,
                bool auth_only = false);

  // Initiator's first frame payload.
  std::string initiator_hello();
  // Responder: process hello_i -> hello_r payload; nullopt + error() on
  // failure (version mismatch, plaintext peer, bad ephemeral).
  std::optional<std::string> on_hello(const Json& obj);
  // Initiator: process hello_r -> auth payload; channel established.
  std::optional<std::string> on_hello_reply(const Json& obj);
  // Responder: process auth_i; channel established.
  bool on_auth(const Json& obj);

  std::string seal_frame(const std::string& payload);
  // nullopt on AEAD failure: the connection must drop.
  std::optional<std::string> open_frame(const std::string& payload);

  bool established() const { return established_; }
  int64_t peer_id() const { return peer_id_; }
  const std::string& error() const { return error_; }
  // Fast-path negotiation surface (ISSUE 14): auth-only flavor, the
  // peer's hello offer, both-sides-offered, and the per-direction
  // session keys (valid once established).
  bool auth_only() const { return auth_only_; }
  bool mac_negotiated() const {
    return wire_offer_mac(offer_mac_) && peer_offers_mac_;
  }
  const uint8_t* auth_send_key() const { return auth_send_key_; }
  const uint8_t* auth_recv_key() const { return auth_recv_key_; }

  // {"type":"reject","reason":...,"ver":...} payload for clean refusal.
  static std::string reject_payload(const std::string& reason);
  // Version-check-only hello for plaintext clusters.
  static std::string plain_hello(int64_t my_id, bool offer_mac = false);
  // Shared version gate; sets *err on mismatch.
  static bool check_version(const Json& obj, std::string* err);

 private:
  void transcript(uint8_t out[32]) const;
  bool verify_peer_sig(const Json& obj, const char* label);
  bool finish();

  const ClusterConfig* cfg_;
  int64_t my_id_;
  uint8_t seed_[32];
  bool initiator_;
  int64_t expected_peer_;
  int64_t peer_id_ = -1;
  uint8_t eph_secret_[32];
  uint8_t eph_pub_[32];
  uint8_t peer_eph_[32];
  bool have_peer_eph_ = false;
  uint8_t send_key_[64];
  uint8_t recv_key_[64];
  uint8_t auth_send_key_[32];
  uint8_t auth_recv_key_[32];
  uint64_t send_ctr_ = 0;
  uint64_t recv_ctr_ = 0;
  bool established_ = false;
  bool offer_mac_ = false;
  bool auth_only_ = false;
  bool peer_offers_mac_ = false;
  // The transcript binds to the INITIATOR's advertised version (both
  // sides know it after hello_i), so 1.1.0 <-> 1.0.0 handshakes agree on
  // the signed bytes. Initiator: the version it sent; responder: set
  // from hello_i in on_hello.
  std::string hs_version_;
  std::string error_;
};

}  // namespace pbft
